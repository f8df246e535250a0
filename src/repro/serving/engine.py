"""Serving engine: the paper's GPU server as the dispatch layer of a JAX
inference runtime — a multi-server pool with continuous, PAGED, length-aware
decode batching.

Architecture (one engine per host; server ``i`` runs on
``jax.local_devices()[i % n]`` with its own copy of the parameters, its own
cache pools and its own compiled step programs):

  client streams ──admit──▶ PoolAdmissionController (Eqs (1)-(6) per
        │                   device partition; device-assignment = WFD on
        │                   declared accelerator utilization)
        └──submit──▶ ServerPool ──▶ AcceleratorServer / BatchingServer
                         │            (priority queue, §5.1; one request —
                         │             or one BATCH — at a time: XLA is
                         ▼             non-preemptive, like the paper's GPU)
              jitted prefill / batched decode steps
                         │
         completion ─────┘ clients suspended on Request.wait()

  * Each stream declares (period, deadline, segment WCETs); admission pins
    it to one server (partitioned, like the paper's per-core partitioning)
    and the pool router follows that assignment for the stream's lifetime.
  * Continuous decode batching (``batching=True``): decode steps from all
    streams assigned to a server coalesce into ONE device call (amortizing
    Lemma 1's 2*eps per request to 2*eps per batch).  Two cache layouts:

    masked-dense (default): one slot cache of ``max_batch`` dense rows;
      every step runs over the full (max_batch, max_seq) buffer with
      inactive rows masked and carried through untouched.

    paged (``paged=True``): per-server KV block POOLS (num_blocks,
      block_size, n_kv, head_dim) per layer, with ``PagedKVCacheManager``
      owning the host-side block accounting.  Each step the engine builds a
      COMPACT batch of only the live rows (slot compaction — padded to the
      next power of two, never to max_batch) and a block-table gather whose
      width covers only the live rows' true lengths (bucketed to a power of
      two).  Device cost scales with actual outstanding work — the paper's
      central-knowledge argument (§7) pushed into the device hot path.
      Greedy tokens stay bit-identical to the unbatched dense path: masked
      tail columns contribute exactly zero to the softmax, and pool rows are
      scattered disjointly (no masked merge at all).

  * Batched prefill: prefills are length-bucketed — ``batch_key =
    ("prefill", si, bucket)`` with ``bucket`` the power-of-two pad length —
    so same-bucket prompts from concurrent streams coalesce into one device
    call through the same BatchingServer discipline.  Per-row true lengths
    ride in the batch and become the cache's per-row ``pos``.
  * Greedy pick on the device: the batched prefill and decode programs
    return each row's next token id, so a step copies n int32 ids to the
    host, never n rows of full-vocabulary logits.
  * Per-stream sequence state (generated tokens, the last token, lengths,
    block tables, latencies) lives in the calling thread, never in the
    batch: payloads carry only (token, table, length).
  * Straggler mitigation: DeadlineAwarePolicy can bump a stream's priority
    or the engine can run the servers in EDF mode.
"""

from __future__ import annotations

import math
import threading
import time
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.cost_model import autotune_buckets, bucket_up
from repro.core.admission import PoolAdmissionController
from repro.core.dispatch.pool import ServerPool
from repro.core.faults import ServerFailedError, StreamShedError
from repro.core.spans import Recorder
from repro.core.task_model import GpuSegment, Task
from repro.models import model as M
from repro.models.moe import routes_sparsely
from repro.runtime.straggler import DeadlineAwarePolicy, StepTimeWatchdog
from repro.serving.kvcache import (FAMILIES, OutOfBlocksError,
                                   PagedKVCacheManager)


def _pow2ceil(n: int) -> int:
    """Smallest power of two >= n (>= 1): the shape-bucketing rule for
    compacted batch rows, prefill pad lengths, and block-table widths —
    bounds the number of distinct jit traces to O(log) per dimension."""
    return 1 << max(n - 1, 0).bit_length()


def _pow2_ladder(cap: int) -> tuple[int, ...]:
    """Every bucket the pow2-with-clamp rule can produce up to ``cap``:
    1, 2, 4, ... plus ``cap`` itself when cap is not a power of two (the
    runtime clamps ``_pow2ceil`` to the cap, so e.g. max_batch=6 makes the
    live-row counts 5..6 land in a SIX-row cell, not an eight-row one)."""
    out = []
    v = 1
    while v < cap:
        out.append(v)
        v *= 2
    out.append(cap)
    return tuple(out)


def _greedy(logits):
    """Each row's greedy token at its last position: (n, T, V) logits ->
    (n,) int32, the first maximum on ties, as ``np.argmax`` picks."""
    return jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)


@dataclass
class PrecompileReport:
    """What one ``precompile()`` call did: ``compiled`` distinct traces
    warmed now, ``skipped`` reachable/requested cells NOT traced (already
    warm from an earlier call, or filtered out by the traffic model)."""

    compiled: int = 0
    skipped: int = 0
    decode_cells: tuple = ()
    prefill_cells: tuple = ()
    migrate_cells: tuple = ()


@dataclass
class _WarmCells:
    """Shape cells compiled on one device.  Compiled programs are per
    device, so each device keeps its own sets; servers that share a device
    share them."""

    decode: set = field(default_factory=set)  # (rows, width)
    prefill: set = field(default_factory=set)  # (rows, bucket)
    migrate: set = field(default_factory=set)  # width


@dataclass
class StreamSpec:
    name: str
    priority: int
    period_ms: float
    deadline_ms: float
    # declared worst-case segment costs for admission (measured or profiled)
    prefill_ms: float
    decode_ms: float
    decode_steps: int  # decode segments per job (period)
    cpu_ms: float = 0.1


@dataclass
class GenerationResult:
    tokens: list[int] = field(default_factory=list)
    prefill_latency_s: float = 0.0
    decode_latencies_s: list[float] = field(default_factory=list)
    recoveries: int = 0  # server deaths this job survived
    # the prefill's greedy token: fed to decode step 0, not in ``tokens``
    first_token: int | None = None
    # monotonic timestamp per recovery at which the retained prefix was
    # re-established on a survivor (resume point, for latency measurement)
    resumed_at_monotonic: list[float] = field(default_factory=list)
    # monotonic time the first token was known (after the prefill's argmax)
    first_token_at: float | None = None
    # seconds blocked waiting for a decode slot, over every attempt
    slot_wait_s: float = 0.0


@dataclass
class _RecoveryLog:
    """Per-stream-job recovery state: the RETAINED TOKEN PREFIX.

    The first attempt's prefill argmax (``first_token``) is fed to decode
    step 0 but never appended to the result; every decode argmax is
    appended to both the result and ``generated``.  The retained prefix —
    prompt ++ [first_token] ++ generated — is therefore exactly the token
    sequence whose KV the dead server held, so re-prefilling it on a
    survivor puts the cache in the same state the failed decode step saw,
    and its LAST-position argmax equals the token that step would have
    produced: greedy recovered output is bit-identical by construction."""

    prompt: np.ndarray
    first_token: int | None = None
    generated: list[int] = field(default_factory=list)

    def retained_prefix(self) -> np.ndarray:
        if self.first_token is None:
            return self.prompt
        return np.concatenate([
            self.prompt,
            np.asarray([self.first_token], np.int32),
            np.asarray(self.generated, np.int32),
        ])


class _SlotState:
    """Per-server decode-slot state for the masked-dense layout (touched
    only on that server's thread, except the free-list, which the engine
    guards with its condition).  The host-side token/mask staging arrays are
    preallocated once — the decode hot loop must not allocate."""

    def __init__(self, max_batch: int):
        self.free = list(range(max_batch))
        self.cache = None  # lazily built (max_batch rows)
        self.cond = threading.Condition()
        self.tok_scratch = np.zeros((max_batch, 1), np.int32)
        self.active_scratch = np.zeros((max_batch,), bool)


class _PagedState:
    """Per-server paged-cache state: the host-side allocator (blocks, state
    slabs, shared segments — whichever kinds the cache family uses) plus the
    device pools.  ``mgr``/``lock`` are touched from client threads at job
    start/end; ``pools`` and the staging buffers only ever from the server's
    own thread (serialized with its batches)."""

    def __init__(self, cfg, num_blocks: int, block_size: int, max_batch: int,
                 max_seq: int, *, family: str = "gqa", num_slabs: int = 0,
                 num_segments: int = 0):
        self.family = FAMILIES[family]
        self.mgr = PagedKVCacheManager(num_blocks=num_blocks,
                                       block_size=block_size,
                                       num_slabs=num_slabs,
                                       num_segments=num_segments,
                                       family=family)
        self.lock = threading.Lock()
        # table width covering max_seq (0 for slab-only families)
        self.nb_max = max_seq // block_size if self.family.uses_blocks else 0
        # one resource of EACH kind the family uses is held back as the
        # scratch target for padded scatter lanes / unused packed columns;
        # nothing ever reads scratch content
        self.mgr.allocate("__scratch__", 1)
        scratch = self.mgr.seqs["__scratch__"]
        self.scratch_block = scratch.blocks[0] if scratch.blocks else 0
        self.scratch_slab = scratch.slab if scratch.slab is not None else 0
        self.scratch_seg = (scratch.segment if scratch.segment is not None
                            else 0)
        self.pools = None  # lazily built pools dict (family layout)
        # preallocated staging for the compacted decode batch, packed into
        # ONE int32 array so each step pays a single host->device transfer:
        # row = [token, length, slab, segment, block_table...] — a uniform
        # header across families; unused columns carry scratch ids
        self.pack_scratch = np.zeros((max_batch, 4 + self.nb_max), np.int32)


class ServeEngine:
    def __init__(self, cfg, params, *, max_seq: int = 128, batch_size: int = 1,
                 ordering: str = "priority", admission_cores: int = 2,
                 epsilon_ms: float = 0.05, kv_blocks: int = 0,
                 kv_block_size: int = 16, num_servers: int = 1,
                 batching: bool = False, max_batch: int = 8,
                 paged: bool = False, cost_model=None):
        self.cfg = cfg
        self.params = params
        self.max_seq = max_seq
        self.batch_size = batch_size
        self.batching = batching
        self.max_batch = max_batch
        self.cost_model = cost_model
        if paged and not batching:
            raise ValueError("paged=True requires batching=True (the block "
                             "pools are the batched decode cache layout)")
        if paged and not M.supports_paged(cfg):
            raise ValueError(f"paged decode unsupported for {cfg.family}/"
                             f"{cfg.attn_type}; use paged=False (declare a "
                             "cache_family to enable the paged path)")
        # pool kinds the model's cache family uses ({} when not paged):
        # "block" -> growable KV block pool, "slab" -> fixed-size state slab,
        # "segment" -> refcounted read-only shared segment
        self._pool_kinds = M.paged_pool_kinds(cfg) if paged else {}
        self._cache_kinds = set(self._pool_kinds.values())
        if paged and "block" in self._cache_kinds and max_seq % kv_block_size:
            raise ValueError(f"max_seq={max_seq} must be a multiple of "
                             f"kv_block_size={kv_block_size} for the paged "
                             "layout")
        self.paged = paged
        # family-tagged cost-model phases: GQA keeps the untagged names
        # (back-compat with every recorded cell); other families get their
        # own fit groups so one family's timing never pollutes another's
        self._family = (M.cache_family(cfg) or "gqa") if paged else "gqa"
        _tag = "" if self._family == "gqa" else "@" + self._family
        self._decode_kind = "decode" + _tag
        self._prefill_kind = "prefill" + _tag
        self._migrate_kind = "migrate" + _tag
        self.kv_block_size = kv_block_size
        # server i -> local device i % n; ``params`` are copied to a device
        # the first time a server there needs them (see _params_on)
        self._devices = [self._device_for(i) for i in range(num_servers)]
        self._placed: dict = {}
        self._place_lock = threading.Lock()
        self.pool = ServerPool(num_servers, ordering=ordering,
                               batching=batching, max_batch=max_batch,
                               name="serve-engine")
        self.admission = PoolAdmissionController(
            num_servers, cores_per_device=admission_cores,
            epsilon_ms=epsilon_ms, cost_model=cost_model)
        self.straggler = DeadlineAwarePolicy()
        # core.spans.Recorder while the served path is traced, else None
        self.recorder: Recorder | None = None
        # optional paged-KV accounting for the UNBATCHED path: generate()
        # holds block allocations for its sequence's lifetime; exhaustion
        # rejects the request before any device work is dispatched
        # (backpressure at the cache, not OOM).  The paged BATCHED path uses
        # per-server managers instead (see _PagedState).
        self.kv = (PagedKVCacheManager(num_blocks=kv_blocks,
                                       block_size=kv_block_size)
                   if kv_blocks and not self.paged else None)
        self._kv_lock = threading.Lock()
        self._seq_counter = 0
        # fault-tolerance state (see enable_fault_tolerance): recovery is
        # serialized — concurrent failure observers queue on the lock and
        # find the server already handled
        self._recovery_lock = threading.Lock()
        self._shed: set[str] = set()
        self._held: dict[str, set] = {}  # stream -> {(si | None, seq_id)}
        self.degraded_reports: list = []
        # migration state: _mig_lock serializes every _held mutation the
        # migration protocol and remove() can race on (see
        # _execute_migration); _active_jobs is the per-server active-stream
        # depth signal the work-stealing rebalancer reads
        self._mig_lock = threading.Lock()
        self._active_jobs: dict[str, int] = {}
        self._ft_params: dict | None = None  # set by enable_fault_tolerance
        self._steal_stop: threading.Event | None = None
        self._steal_min_gain_ms = 0.0
        self.migrations_completed = 0
        # max_seq must be static inside the trace (it sizes the cache pad)
        self._prefill = jax.jit(
            lambda p, b: M.apply(cfg, p, {**b, "max_seq": max_seq},
                                 mode="prefill"))
        self._decode = jax.jit(
            lambda p, b, c: M.apply(cfg, p, b, mode="decode", cache=c))
        # each prefill row's greedy token at its true last position, picked
        # on the device so only n int32 ids reach the host — jitted so the
        # pick compiles once per prefill cell (with it, in precompile)
        # rather than eagerly per live-row count mid-traffic
        self._last_token = jax.jit(
            lambda logits, lens: _greedy(jnp.take_along_axis(
                logits, (lens - 1)[:, None, None], axis=1)))
        self._streams: dict[str, StreamSpec] = {}
        # shape-bucket boundaries (tunable via tune_buckets()): batch rows
        # and prefill pad lengths default to the full pow2 ladder — exactly
        # the cells the pow2-with-clamp rules could already produce
        self._row_buckets = _pow2_ladder(max_batch)
        self.prefill_buckets = _pow2_ladder(max_seq)
        self.width_buckets: tuple[int, ...] = ()
        # cells warmed by precompile(), per device; consulted by the
        # safe-fallback bump-up in the hot path
        self._warm: dict = {}
        if batching:
            self._slots = [_SlotState(max_batch) for _ in range(num_servers)]
            self._batch_axes = _cache_batch_axes(cfg, max_seq)
            self._insert_jit = jax.jit(self._insert_impl)
            self._decode_masked = jax.jit(self._decode_masked_impl)
        if self.paged:
            uses_blocks = "block" in self._cache_kinds
            blocks_per_seq = max_seq // kv_block_size if uses_blocks else 0
            # default block pool: every slot can hold a max_seq sequence,
            # plus the scratch block (slab-only families carry no blocks)
            num_blocks = (kv_blocks or (max_batch * blocks_per_seq + 1)
                          if uses_blocks else 0)
            # slabs: one per slot, doubled so an in-flight migration can
            # hold src+dst at once, plus scratch; segments: shared across
            # slots (refcounted) so max_batch distinct keys + scratch cover
            # the worst case
            num_slabs = (2 * max_batch + 2
                         if "slab" in self._cache_kinds else 0)
            num_segments = (max_batch + 2
                            if "segment" in self._cache_kinds else 0)
            # remembered for elastically-added servers
            self._num_blocks = num_blocks
            self._num_slabs = num_slabs
            self._num_segments = num_segments
            self._paged = [
                _PagedState(cfg, num_blocks, kv_block_size, max_batch,
                            max_seq, family=self._family,
                            num_slabs=num_slabs, num_segments=num_segments)
                for _ in range(num_servers)
            ]
            # slab-only families have no gather width: the single 0 bucket
            # keeps every bucket_up() call well-defined
            self.width_buckets = (_pow2_ladder(self._paged[0].nb_max)
                                  if uses_blocks else (0,))
            # the pools argument is donated in both jits: pool updates must
            # alias, not copy — the pool is owned by the server thread and
            # immediately replaced by the call's output
            self._insert_paged_jit = jax.jit(self._insert_paged_impl,
                                             donate_argnums=(0,))
            self._decode_paged = jax.jit(self._decode_paged_impl_greedy,
                                         donate_argnums=(2,))
            # migration primitive: gather a stream's live blocks into one
            # packed buffer (source server), scatter them into fresh blocks
            # (destination server).  Gather must NOT donate (the source
            # pool stays live until commit); scatter donates like insert.
            self._export_kv = jax.jit(self._export_kv_impl)
            self._import_kv = jax.jit(self._import_kv_impl,
                                      donate_argnums=(0,))

    def enable_tracing(self, recorder: Recorder | None = None) -> Recorder:
        """Record spans and counters of the served path into ``recorder``
        (a new one by default), shared by the engine and every server;
        returns it.  A job already in its decode phase is not counted in
        its server's ``ready`` counter."""
        rec = recorder if recorder is not None else Recorder()
        self.recorder = rec
        for server in self.pool.servers:
            server.set_recorder(rec)
        return rec

    def disable_tracing(self) -> None:
        self.recorder = None
        for server in self.pool.servers:
            server.set_recorder(None)

    @staticmethod
    def _device_for(si: int):
        local = jax.local_devices()
        return local[si % len(local)]

    def device_of(self, si: int):
        """The device server ``si`` runs on."""
        return self._devices[si]

    def _params_on(self, si: int):
        """``params`` committed to server ``si``'s device (one copy per
        device, made on first use)."""
        dev = self._devices[si]
        with self._place_lock:
            placed = self._placed.get(dev)
            if placed is None:
                placed = self._placed[dev] = jax.device_put(self.params, dev)
        return placed

    def _put(self, si: int, tree):
        """Host staging arrays -> server ``si``'s device."""
        return jax.device_put(tree, self._devices[si])

    def _zeros_on(self, si: int, make):
        """A zero cache pytree built on server ``si``'s device."""
        dev = self._devices[si]
        with jax.default_device(dev):
            return jax.device_put(make(), dev)

    def _moe_tag(self, n_tokens: int) -> dict:
        """Tag of an ``engine.device`` span: whether a call of ``n_tokens``
        tokens reads only the routed experts (``moe_routed``); none for a
        config without experts."""
        if not self.cfg.is_moe:
            return {}
        return {"moe_routed": routes_sparsely(self.cfg, n_tokens)}

    def _warm_of(self, si: int) -> _WarmCells:
        return self._warm.setdefault(self._devices[si], _WarmCells())

    @property
    def server(self):
        """The first pool server (single-server back-compat alias)."""
        return self.pool.servers[0]

    # -- stream admission (analysis-driven, Eqs (1)-(6) per partition) -----
    def admit(self, spec: StreamSpec, *, cell=None):
        """``cell``: optional cost-model shape hint (one CellKey broadcast
        to all segments, or a per-segment sequence) enabling CALIBRATED
        admission when the engine was built with a ``cost_model`` — declared
        worst-case segment costs are re-priced to the measured/interpolated
        cost of the bucket the stream actually runs in (never upward)."""
        segs = (GpuSegment(e=spec.prefill_ms * 0.9, m=spec.prefill_ms * 0.1),
                *(GpuSegment(e=spec.decode_ms * 0.9, m=spec.decode_ms * 0.1),)
                * spec.decode_steps)
        task = Task(name=spec.name, C=spec.cpu_ms, T=spec.period_ms,
                    D=spec.deadline_ms, segments=segs, priority=spec.priority)
        decision, device = self.admission.try_admit(task, cell=cell)
        if decision.admitted:
            self._streams[spec.name] = spec
            self.straggler.register(spec.name, spec.deadline_ms)
            # the router follows the admission's device-assignment step
            self.pool.assign(spec.name, utilization=task.G / task.T,
                             priority=spec.priority, server=device)
        return decision

    def remove(self, name: str) -> None:
        """Withdraw a stream: admission slot, router binding, and any
        paged-KV blocks still held for it (a stream evicted by failure or
        shed by degraded admission may leave reservations behind if its
        generating thread is gone; ``missing_ok`` makes the free race-safe
        against that thread's own cleanup).  Never call while the stream
        has a device call in flight.

        The held-blocks sweep runs under ``_mig_lock`` so it is atomic
        w.r.t. an in-flight migration of this stream: during the copy
        window the ledger holds BOTH (src, seq) and (dst, seq); freeing
        both here is exactly right (the stream is gone), and the migrating
        thread's commit re-checks the ledger under the same lock and
        aborts instead of double-freeing (see _execute_migration)."""
        self.admission.remove(name)
        self.pool.remove(name)
        self._streams.pop(name, None)
        self._shed.discard(name)
        self._active_jobs.pop(name, None)
        with self._mig_lock:
            held = self._held.pop(name, set())
            for si, seq_id in held:
                if si is None:
                    with self._kv_lock:
                        self.kv.free_seq(seq_id, missing_ok=True)
                else:
                    state = self._paged[si]
                    with state.lock:
                        state.mgr.free_seq(seq_id, missing_ok=True)

    # -- bucket auto-tuning (cost-model driven) ----------------------------
    def tune_buckets(self, prompt_lengths, *, steps_hint: int = 0,
                     cost_model=None, max_buckets: int = 4):
        """Pick the prefill-length and (paged) gather-width bucket
        boundaries for an expected workload: exact DP over the pow2
        candidate ladder minimizing total padding waste — or, when a fitted
        ``cost_model`` (default: the engine's own) can price the phase,
        total PREDICTED step cost, which weights waste by what it actually
        costs on this device.  The largest candidate always survives
        (coverage), so runtime clamping semantics are unchanged.  Call
        BEFORE precompile()/traffic — retuning invalidates warm cells, so
        this clears both warm sets.  Returns (prefill_buckets,
        width_buckets)."""
        model = cost_model if cost_model is not None else self.cost_model
        lengths = [int(l) for l in prompt_lengths]
        if any(l > self.max_seq for l in lengths):
            raise ValueError("prompt length exceeds max_seq")

        def priced(phase, rows):
            if model is None:
                return None
            probe = model.predict(phase, rows, _pow2_ladder(self.max_seq)[-1])
            if not math.isfinite(probe):
                return None  # phase unmeasured: fall back to padding waste
            return lambda bucket, value: model.predict(phase, rows, bucket)

        self.prefill_buckets = autotune_buckets(
            lengths or [1], _pow2_ladder(self.max_seq),
            max_buckets=max_buckets, cost_of=priced(self._prefill_kind, 1))
        if self.paged and self._paged[0].nb_max:
            bs = self.kv_block_size
            nb_max = self._paged[0].nb_max
            # widths are driven by each stream's FINAL length (the widest
            # gather its decode steps reach): ceil((len + steps + 1) / bs)
            needs = [min(nb_max, -(-(l + steps_hint + 1) // bs))
                     for l in lengths] or [1]
            wmodel = None
            if model is not None:
                probe = model.predict(self._decode_kind, 1, nb_max)
                if math.isfinite(probe):
                    wmodel = lambda bucket, value: model.predict(
                        self._decode_kind, 1, bucket)
            self.width_buckets = autotune_buckets(
                needs, _pow2_ladder(nb_max), max_buckets=max_buckets,
                cost_of=wmodel)
        for warm in self._warm.values():
            warm.decode.clear()
            warm.prefill.clear()
        return self.prefill_buckets, self.width_buckets

    def traffic_cells(self, requests, *, concurrency: int) -> set:
        """The shape cells a known workload can hit: ``requests`` are
        (prompt_length, decode_steps) pairs, at most ``concurrency`` of them
        in flight on one server at a time.  Pass the result as
        ``precompile(traffic=...)`` (after any ``tune_buckets``) to compile
        exactly these cells plus each phase's fallback."""
        rows = {bucket_up(n, self._row_buckets)
                for n in range(1, concurrency + 1)}
        bs = self.kv_block_size
        nb_max = self._paged[0].nb_max if self.paged else 0
        cells = set()
        for length, steps in requests:
            bucket = bucket_up(length, self.prefill_buckets)
            cells |= {(self._prefill_kind, r, bucket) for r in rows}
            if not self.paged:
                continue
            for pos in range(length, length + steps):
                need = -(-(pos + 1) // bs) if nb_max else 0
                w = bucket_up(need, self.width_buckets)
                cells |= {(self._decode_kind, r, w) for r in rows}
        return cells

    # -- static cell pricing (hlo_cost -> cost-model features) -------------
    def lower_cells(self, cells, *, sharding=None) -> dict:
        """Lower each cell's step programs from shapes alone (no device
        execution, no parameter placement).  Returns {CellKey: [Lowered,
        ...]}: ``("decode", rows, width)`` -> the paged decode step;
        ``("prefill", rows, bucket)`` -> the bucketed prefill and its
        last-position pick;
        ``("insert", rows, bucket)`` -> the scatter of such a prefill's
        cache into the pools; ``("migrate", width, block_size)`` -> the
        gather and the scatter of one migration.  ``sharding`` places every
        argument (e.g. a device of a described TPU topology, to compile for
        a chip that is not attached); default: the default device.  Paged
        engines only."""
        if not self.paged:
            raise ValueError("lower_cells requires paged=True")

        def spec(x):
            return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)

        def specs(tree):
            return jax.tree.map(spec, tree)

        params = specs(self.params)
        pools = specs(jax.eval_shape(
            lambda: M.init_paged_cache(self.cfg, self._num_blocks,
                                       self.kv_block_size,
                                       num_slabs=self._num_slabs,
                                       num_segments=self._num_segments)))
        idx = spec(jax.ShapeDtypeStruct((), jnp.int32))
        out: dict[tuple, list] = {}
        for cell in cells:
            phase, a, b = cell
            base = phase.split("@", 1)[0]  # family-tagged phases lower alike
            if base == "migrate":
                table = spec(jax.ShapeDtypeStruct((a,), jnp.int32))
                packed = specs(jax.eval_shape(self._export_kv_impl, pools,
                                              table, idx, idx))
                out[cell] = [
                    self._export_kv.lower(pools, table, idx, idx),
                    self._import_kv.lower(pools, packed, table, idx, idx)]
            elif base == "decode":
                packed = spec(jax.ShapeDtypeStruct((a, 4 + b), jnp.int32))
                out[cell] = [self._decode_paged.lower(params, packed, pools)]
            elif base in ("prefill", "insert"):
                batch = specs(self._prefill_batch(np.zeros((a, b), np.int32),
                                                  np.ones((a,), np.int32)))
                logits, cache = specs(jax.eval_shape(self._prefill, params,
                                                     batch)[:2])
                if base == "prefill":
                    out[cell] = [self._prefill.lower(params, batch),
                                 self._last_token.lower(logits,
                                                        batch["lengths"])]
                    continue
                table = spec(jax.ShapeDtypeStruct((self._paged[0].nb_max,),
                                                  jnp.int32))
                out[cell] = [self._insert_paged_jit.lower(
                    pools, cache, idx, table, idx, idx)]
            else:
                raise ValueError(f"unknown phase in cell {cell!r}")
        return out

    def static_cell_costs(self, cells=None) -> dict:
        """Price shape cells STATICALLY: compile each cell's programs (see
        ``lower_cells``; no device execution) and walk the optimized HLO
        with ``analysis.hlo_cost`` for exact per-cell (flops, hbm_bytes),
        summed over the cell's programs.  Returns {CellKey: (flops,
        hbm_bytes)} ready for ``cost_model.hlo_cell_features`` — the feed
        that lets a ``StepCostModel`` price a migration/scatter width (or
        any cell) it never measured at runtime off static analysis instead
        of the declared worst case.

        ``cells`` default: every migrate width bucket — the cells a steal
        can hit cold.  Paged engines only (the masked-dense decode has a
        single full-shape cell that measurement always covers)."""
        from repro.analysis import hlo_cost

        if not self.paged:
            raise ValueError("static_cell_costs requires paged=True")
        if cells is None:
            cells = [(self._migrate_kind, w, self.kv_block_size)
                     for w in self.width_buckets]
        out: dict[tuple, tuple[float, float]] = {}
        for cell, programs in self.lower_cells(cells).items():
            costs = [hlo_cost.analyze_text(lo.compile().as_text())
                     for lo in programs]
            out[cell] = (sum(c.flops for c in costs),
                         sum(c.hbm_bytes for c in costs))
        return out

    # -- batched decode internals (masked-dense layout) --------------------
    def _insert_impl(self, full, batched, src_row, slot):
        """Copy row ``src_row`` of a (possibly coalesced) prefill cache into
        row ``slot`` of the slot cache."""

        def one(f, o, ax):
            row = jax.lax.dynamic_slice_in_dim(o, src_row, 1, axis=ax)
            return jax.lax.dynamic_update_slice_in_dim(
                f, row.astype(f.dtype), slot, axis=ax)

        return jax.tree.map(one, full, batched, self._batch_axes)

    def _decode_masked_impl(self, params, tokens, cache, active):
        """One batched decode step over the slot cache, returning each row's
        greedy token id; rows where ``active`` is False keep their previous
        cache (and their ids are garbage, discarded by the caller)."""
        logits, new_cache, _ = M.apply(self.cfg, params, {"tokens": tokens},
                                       mode="decode", cache=cache)

        def merge(o, n, ax):
            shape = [1] * n.ndim
            shape[ax] = n.shape[ax]
            return jnp.where(active.reshape(shape), n, o)

        return _greedy(logits), jax.tree.map(merge, cache, new_cache,
                                             self._batch_axes)

    def _acquire_slot(self, si: int) -> int:
        state = self._slots[si]
        with state.cond:
            while not state.free:
                state.cond.wait()
            return state.free.pop()

    def _try_acquire_slot(self, si: int) -> int | None:
        """Non-blocking slot acquisition — the migration path must never
        deadlock holding its source slot while waiting on a destination
        slot, so no free slot means the steal is cancelled instead."""
        state = self._slots[si]
        with state.cond:
            if not state.free:
                return None
            return state.free.pop()

    def _release_slot(self, si: int, slot: int) -> None:
        state = self._slots[si]
        with state.cond:
            state.free.append(slot)
            state.cond.notify()

    def _insert_slot(self, si: int, slot: int, cache, src_row: int) -> None:
        """Runs on server ``si``'s thread (serialized with its batches)."""
        state = self._slots[si]
        if state.cache is None:
            state.cache = self._make_slot_cache(si)
        src_row, slot = self._put(si, (np.int32(src_row), np.int32(slot)))
        state.cache = jax.block_until_ready(
            self._insert_jit(state.cache, cache, src_row, slot))

    def _run_decode_batch(self, si: int):
        """run_batch callable for server ``si`` (masked-dense): payloads are
        (slot, token) pairs; ONE masked device call serves them all, and
        each result is that slot's next token id.  The staging arrays are
        the slot state's preallocated scratch — no per-step host
        allocation."""

        def run(payloads):
            state = self._slots[si]
            toks, active = state.tok_scratch, state.active_scratch
            toks[:, 0] = 0
            active[:] = False
            for slot, token in payloads:
                toks[slot, 0] = token
                active[slot] = True
            toks_d, active_d = self._put(si, (toks, active))
            ids, state.cache = jax.block_until_ready(
                self._decode_masked(self._params_on(si), toks_d,
                                    state.cache, active_d))
            ids = np.asarray(ids)
            return [int(ids[slot]) for slot, _ in payloads]

        return run

    # -- batched decode internals (paged pool layouts, family-generic) -----
    def _make_slot_cache(self, si: int):
        return self._zeros_on(si, lambda: M.init_cache(
            self.cfg, self.max_batch, self.max_seq))

    def _make_pools(self, si: int):
        mgr = self._paged[si].mgr
        return self._zeros_on(si, lambda: M.init_paged_cache(
            self.cfg, mgr.num_blocks, mgr.block_size,
            num_slabs=mgr.num_slabs, num_segments=mgr.num_segments))

    def _insert_paged_impl(self, pools, cache, src_row, table, slab, seg):
        """Scatter row ``src_row`` of a prefill cache into the pools,
        dispatched per pool kind: "block" entries land at ``table`` (nb_max
        entries; lanes past the sequence's reserved blocks point at the
        scratch block and carry all-zero rows, so duplicate scatter lanes
        stay deterministic); "slab" entries land in row ``slab``; "segment"
        entries in row ``seg`` (shared segments — re-staging an
        already-present key rewrites identical content, idempotent)."""
        bs = self.kv_block_size
        views = M.paged_insert_views(self.cfg, cache)

        def block_one(pool, leaf):
            # leaf (L, B, max_seq, ...) -> rows (L, nb_max, bs, ...)
            rows = jax.lax.dynamic_index_in_dim(leaf, src_row, axis=1,
                                                keepdims=False)
            rows = rows.reshape(leaf.shape[0], -1, bs, *leaf.shape[3:])
            return pool.at[:, table].set(rows.astype(pool.dtype))

        def row_one(idx):
            def f(pool, leaf):
                row = jax.lax.dynamic_index_in_dim(leaf, src_row, axis=1,
                                                   keepdims=True)
                return jax.lax.dynamic_update_slice_in_dim(
                    pool, row.astype(pool.dtype), idx, axis=1)
            return f

        out = {}
        for key, kind in self._pool_kinds.items():
            one = (block_one if kind == "block"
                   else row_one(slab if kind == "slab" else seg))
            out[key] = jax.tree.map(one, pools[key], views[key])
        return out

    def _decode_paged_impl(self, params, packed, pools):
        """One compacted paged decode step.  ``packed`` (n, 4+W) int32 rows
        are [token, length, slab, segment, block_table...] — a uniform
        header across cache families; columns a family doesn't use carry
        scratch ids and are never read.  The table width W addresses only
        the gather the live rows need; rows scatter their new KV / state
        into their own blocks/slabs (disjoint by construction — no masked
        merge).  The pool buffers are DONATED by the caller: the update
        aliases in place instead of copying the whole pool every token."""
        tokens, lengths = packed[:, :1], packed[:, 1]
        cache = dict(pools)
        cache["pos"] = lengths
        if "block" in self._cache_kinds:
            cache["block_tables"] = packed[:, 4:]
        if "slab" in self._cache_kinds:
            cache["slab_ids"] = packed[:, 2]
        if "segment" in self._cache_kinds:
            cache["segment_ids"] = packed[:, 3]
        logits, new_cache, _ = M.apply(self.cfg, params, {"tokens": tokens},
                                       mode="decode", cache=cache)
        return logits, {k: new_cache[k] for k in self._pool_kinds}

    def _decode_paged_impl_greedy(self, params, packed, pools):
        """``_decode_paged_impl`` with the greedy pick on the device: each
        row's next token id, (n,) int32, in place of its (n, 1, V) logits,
        so a step sends n ids to the host rather than n vocabulary rows."""
        logits, new_pools = self._decode_paged_impl(params, packed, pools)
        return _greedy(logits), new_pools

    def _insert_slot_paged(self, si: int, cache, src_row: int,
                           table: np.ndarray, slab: int = 0,
                           seg: int = 0) -> None:
        """Runs on server ``si``'s thread (serialized with its batches)."""
        state = self._paged[si]
        if state.pools is None:
            state.pools = self._make_pools(si)
        rec = self.recorder
        if rec is not None:
            span = rec.begin("engine.stage")
        args = self._put(si, (np.int32(src_row), table, np.int32(slab),
                              np.int32(seg)))
        if rec is not None:
            rec.end(span)
            span = rec.begin("engine.device")
        state.pools = jax.block_until_ready(
            self._insert_paged_jit(state.pools, cache, *args))
        if rec is not None:
            rec.end(span)

    def _run_paged_decode(self, si: int):
        """run_batch callable for server ``si`` (paged): payloads are
        (token, block_table, length, slab, segment) tuples, and each result
        is that row's next token id, picked on the device.  Slot compaction
        + length bucketing happen here: only the live rows enter the device
        call (padded to the next power of two by duplicating row 0 —
        duplicate scatter lanes write identical values and slabs are
        per-row-owned, so padding is idempotent), and the block-table gather
        is truncated to the power-of-two width that covers the longest live
        row (0 for slab-only families: no gather axis at all)."""

        def run(payloads):
            rec = self.recorder
            if rec is not None:
                span = rec.begin("engine.stage")
            state = self._paged[si]
            bs = state.mgr.block_size
            n = len(payloads)
            n_pad = bucket_up(n, self._row_buckets)
            need = (max(-(-(length + 1) // bs)
                        for _, _, length, _, _ in payloads)
                    if state.nb_max else 0)
            w = bucket_up(need, self.width_buckets)
            # safe fallback: a cold cell mid-traffic would stall the server
            # behind XLA compilation, so bump to the cheapest WARM cell that
            # covers it (widening is sound: extra width lanes gather the
            # all-zero scratch block past each row's length, extra rows
            # duplicate row 0 idempotently).  No warm cover -> compile cold.
            cold = False
            warm = self._warm_of(si).decode
            if warm and (n_pad, w) not in warm:
                covers = [c for c in warm if c[0] >= n_pad and c[1] >= w]
                if covers:
                    n_pad, w = min(covers, key=lambda c: c[0] * c[1])
                else:
                    cold = True
            pack = state.pack_scratch
            for i, (token, table, length, slab, seg) in enumerate(payloads):
                pack[i, 0] = token
                pack[i, 1] = length
                pack[i, 2] = slab
                pack[i, 3] = seg
                pack[i, 4:] = table
            for i in range(n, n_pad):  # idempotent padding rows
                pack[i] = pack[0]
            # the recorded call time spans the put, the call and its wait
            t0 = time.monotonic()
            params = self._params_on(si)
            packed = self._put(si, pack[:n_pad, : 4 + w])
            if rec is not None:
                rec.end(span)
                rec.tag(padded=n_pad, width=w)
                span = rec.begin("engine.device", **self._moe_tag(n_pad))
            ids, state.pools = self._decode_paged(params, packed,
                                                  state.pools)
            # queue the ids' copy behind the step, so that they reach the
            # host as the step ends rather than on a fetch issued after it
            ids.copy_to_host_async()
            jax.block_until_ready((ids, state.pools))
            dt = time.monotonic() - t0
            if rec is not None:
                rec.end(span)
            if cold:  # now traced: later hits on this cell are warm
                warm.add((n_pad, w))
            self.pool.servers[si].record_meta(
                kind=self._decode_kind, rows=n, padded=n_pad, width=w,
                compacted=n_pad < self.max_batch, seconds=dt, cold=cold)
            if rec is not None:
                span = rec.begin("engine.fetch")
            ids = np.asarray(ids)
            if rec is not None:
                rec.end(span)
            return [int(ids[i]) for i in range(n)]

        return run

    def _paged_reserve(self, si: int, name: str, prompt_len: int,
                       steps: int, bucket: int
                       ) -> tuple[str, np.ndarray, int, int]:
        """Reserve every resource the job will touch up front (reject early
        rather than stall mid-generation), including the bucketed-prefill
        pad region, whose padding-token KV must land in owned blocks.
        Returns (seq_id, block table, slab id, segment id); kinds the
        family doesn't use come back as the scratch ids."""
        state = self._paged[si]
        with self._kv_lock:
            self._seq_counter += 1
            counter = self._seq_counter
        with state.lock:
            seq_id = f"{name}#{counter}"
            tokens = max(prompt_len + steps, bucket)
            # enc-dec engine frontend stubs every stream's encoder frames
            # as the same zeros (_prefill_batch), so all streams SHARE one
            # cross-attention segment — the COW-dedup the segment pool is
            # for.  Re-staging the shared key rewrites identical content.
            state.mgr.allocate(seq_id, prompt_len, segment_key="__frames__")
            try:
                state.mgr.extend(seq_id, tokens - prompt_len)
            except Exception:
                state.mgr.free_seq(seq_id)
                raise
            alloc = state.mgr.seqs[seq_id]
            table = np.full((state.nb_max,), state.scratch_block, np.int32)
            table[: len(alloc.blocks)] = alloc.blocks
            slab = (alloc.slab if alloc.slab is not None
                    else state.scratch_slab)
            seg = (alloc.segment if alloc.segment is not None
                   else state.scratch_seg)
        self._held.setdefault(name, set()).add((si, seq_id))
        return seq_id, table, slab, seg

    def _paged_release(self, si: int, seq_id: str) -> None:
        name = seq_id.rsplit("#", 1)[0]
        with self._mig_lock:
            held = self._held.get(name)
            if held is not None:
                held.discard((si, seq_id))
            state = self._paged[si]
            with state.lock:
                state.mgr.free_seq(seq_id, missing_ok=True)

    # -- live cache migration (steal / consolidate / elastic drain) --------
    def _export_kv_impl(self, pools, table, slab, seg):
        """Gather one stream's live cache out of every pool into one packed
        contiguous buffer — the single device->host transfer of the
        migration.  Block kinds gather the blocks named by ``table`` (pad
        lanes point at the source scratch block, never-read zeros, so the
        gather width can be pow2-bucketed onto a precompiled cell); slab
        and segment kinds gather their single row."""
        out = {}
        for key, kind in self._pool_kinds.items():
            if kind == "block":
                fn = lambda pool: pool[:, table]
            else:
                idx = slab if kind == "slab" else seg
                fn = (lambda i: lambda pool:
                      jax.lax.dynamic_slice_in_dim(pool, i, 1, axis=1))(idx)
            out[key] = jax.tree.map(fn, pools[key])
        return out

    def _import_kv_impl(self, pools, packed, table, slab, seg):
        """Scatter a packed export into the destination pools: block rows
        at ``table`` (the fresh blocks import_seq allocated; pad lanes
        target the destination scratch block — duplicate scratch writes are
        benign, nothing reads it), the slab row into the FRESH destination
        slab, the segment row into the destination segment (idempotent when
        the key was already resident there).  Donated like the
        decode/insert pool updates."""
        out = {}
        for key, kind in self._pool_kinds.items():
            if kind == "block":
                fn = lambda pool, rows: pool.at[:, table].set(
                    rows.astype(pool.dtype))
            else:
                idx = slab if kind == "slab" else seg
                fn = (lambda i: lambda pool, rows:
                      jax.lax.dynamic_update_slice_in_dim(
                          pool, rows.astype(pool.dtype), i, axis=1))(idx)
            out[key] = jax.tree.map(fn, pools[key], packed[key])
        return out

    def _migrate_cell(self, n_blocks: int, src_si: int,
                      dst_si: int) -> tuple[int, bool]:
        """(padded gather width, cold?) for a migration of ``n_blocks`` —
        same warm-cell bump-up discipline as the decode hot path, over the
        widths warm on BOTH devices (gather on the source, scatter on the
        destination)."""
        w = bucket_up(n_blocks, self.width_buckets)
        warm = self._warm_of(src_si).migrate & self._warm_of(dst_si).migrate
        cold = False
        if warm and w not in warm:
            covers = [c for c in warm if c >= w]
            if covers:
                w = min(covers)
            else:
                cold = True
        return w, cold

    def _execute_migration(self, name: str, seq_id: str, src_si: int,
                           dst_si: int, prio: int):
        """Move ``seq_id``'s live cache (blocks, state slab, shared
        segment — whatever kinds its family uses) from server ``src_si``
        to ``dst_si``; returns (new full-width block table, destination
        slab id, destination segment id).

        Two-phase commit against ``remove()`` (satellite of the protocol in
        ``kvcache``'s docstring): under ``_mig_lock`` the destination
        allocation is made and BOTH sides enter the ``_held`` ledger; the
        copy itself runs outside the lock (a gather on the source server, a
        host hop, a scatter on the destination server — each serialized
        with that server's own batches); commit re-takes the lock,
        verifies the ledger still holds the entries (a concurrent
        ``remove`` frees both sides itself — then this raises instead of
        double-freeing), and frees the source.  Any failure rolls the
        destination back, leaving the stream exactly where it was.

        A ``remove()`` that lands mid-copy may free destination blocks the
        scatter then writes: benign — the scatter targets only blocks this
        migration allocated, their content is never read unless this
        commit succeeds (then they were never freed), and a later owner's
        prefill rewrites every in-range position while attention masks the
        rest."""
        src, dst = self._paged[src_si], self._paged[dst_si]
        rec = self.recorder
        job = rec.current_job() if rec is not None else 0
        with self._mig_lock:
            held = self._held.get(name)
            if held is None or (src_si, seq_id) not in held:
                raise StreamShedError(
                    f"stream {name!r} gone before migration")
            with src.lock:
                exp = src.mgr.export_seq(seq_id)
                src_alloc = src.mgr.seqs[seq_id]
                src_slab = (src_alloc.slab if src_alloc.slab is not None
                            else src.scratch_slab)
                src_seg = (src_alloc.segment
                           if src_alloc.segment is not None
                           else src.scratch_seg)
            with dst.lock:
                # OutOfBlocks -> clean: all-or-nothing across every kind
                new_blocks = dst.mgr.import_seq(exp)
                dst_alloc = dst.mgr.seqs[seq_id]
                dst_slab = (dst_alloc.slab if dst_alloc.slab is not None
                            else dst.scratch_slab)
                dst_seg = (dst_alloc.segment
                           if dst_alloc.segment is not None
                           else dst.scratch_seg)
            held.add((dst_si, seq_id))
        try:
            n = len(exp.blocks)
            w, cold = self._migrate_cell(n, src_si, dst_si)
            src_table = np.full((w,), src.scratch_block, np.int32)
            src_table[:n] = exp.blocks
            dst_table = np.full((w,), dst.scratch_block, np.int32)
            dst_table[:n] = new_blocks

            def gather():
                t0 = time.monotonic()
                args = self._put(src_si, (src_table, np.int32(src_slab),
                                          np.int32(src_seg)))
                packed = jax.block_until_ready(
                    self._export_kv(src.pools, *args))
                packed = jax.tree.map(np.asarray, packed)  # device -> host
                self.pool.servers[src_si].record_meta(
                    kind=self._migrate_kind, rows=n, padded=w,
                    width=self.kv_block_size,
                    seconds=time.monotonic() - t0, cold=cold)
                return packed

            packed = self.pool.servers[src_si].submit(
                gather, priority=prio, name=f"{name}/migrate-export",
                job=job, phase="migrate").wait()

            def scatter():
                if dst.pools is None:
                    dst.pools = self._make_pools(dst_si)
                t0 = time.monotonic()
                args = self._put(dst_si, (packed, dst_table,
                                          np.int32(dst_slab),
                                          np.int32(dst_seg)))
                dst.pools = jax.block_until_ready(
                    self._import_kv(dst.pools, *args))
                self.pool.servers[dst_si].record_meta(
                    kind=self._migrate_kind, rows=n, padded=w,
                    width=self.kv_block_size,
                    seconds=time.monotonic() - t0, cold=cold)

            self.pool.servers[dst_si].submit(
                scatter, priority=prio, name=f"{name}/migrate-import",
                job=job, phase="migrate").wait()
        except BaseException:
            with self._mig_lock:
                held = self._held.get(name)
                if held is not None:
                    held.discard((dst_si, seq_id))
                with dst.lock:
                    dst.mgr.free_seq(seq_id, missing_ok=True)
            raise
        with self._mig_lock:
            held = self._held.get(name)
            if held is None or (dst_si, seq_id) not in held:
                # remove() raced the copy: it freed both sides already
                raise StreamShedError(
                    f"stream {name!r} removed mid-migration")
            held.discard((src_si, seq_id))
            with src.lock:
                src.mgr.free_seq(seq_id, missing_ok=True)
        self.migrations_completed += 1
        full = np.full((dst.nb_max,), dst.scratch_block, np.int32)
        full[:n] = new_blocks
        return full, dst_slab, dst_seg

    # -- batched prefill (length-bucketed) ---------------------------------
    def _run_prefill_batch(self, si: int, bucket: int):
        """run_batch callable coalescing same-bucket prefills: payloads are
        (prompt_row, true_len); ONE device call prefills them all, padded to
        ``bucket``.  Each result is (the greedy token id at the row's last
        position, picked on the device; the coalesced cache; this payload's
        row index) — the caller inserts its row."""

        def run(payloads):
            rec = self.recorder
            if rec is not None:
                span = rec.begin("engine.stage")
            n = len(payloads)
            n_pad = bucket_up(n, self._row_buckets)
            # safe fallback on the ROW axis (the bucket axis was already
            # steered to a warm pad length by _generate_batched): padding
            # rows duplicate row 0 and their outputs are discarded
            cold = False
            warm = self._warm_of(si).prefill
            if warm and (n_pad, bucket) not in warm:
                covers = [r for r, b in warm if b == bucket and r >= n_pad]
                if covers:
                    n_pad = min(covers)
                else:
                    cold = True
            toks = np.zeros((n_pad, bucket), np.int32)
            lens = np.zeros((n_pad,), np.int32)
            for i, (prompt, true_len) in enumerate(payloads):
                toks[i, :true_len] = prompt
                lens[i] = true_len
            for i in range(n, n_pad):  # padding rows: discarded outputs
                toks[i] = toks[0]
                lens[i] = lens[0]
            batch = self._put(si, self._prefill_batch(toks, lens))
            if rec is not None:
                rec.end(span)
                rec.tag(padded=n_pad, bucket=bucket)
                span = rec.begin("engine.device",
                                 **self._moe_tag(n_pad * bucket))
            t0 = time.monotonic()
            logits, cache, _ = jax.block_until_ready(
                self._prefill(self._params_on(si), batch))
            dt = time.monotonic() - t0
            if rec is not None:
                rec.end(span)
            if cold:
                warm.add((n_pad, bucket))
            self.pool.servers[si].record_meta(
                kind=self._prefill_kind, rows=n, padded=n_pad, bucket=bucket,
                seconds=dt, cold=cold)
            if rec is not None:
                span = rec.begin("engine.fetch")
            ids = np.asarray(self._last_token(logits, batch["lengths"]))
            if rec is not None:
                rec.end(span)
            return [(int(ids[i]), cache, i) for i in range(n)]

        return run

    def precompile(self, prompt_buckets: tuple[int, ...] = (), *,
                   traffic=None) -> PrecompileReport:
        """Warm batched-decode/prefill shape cells ahead of time.

        Shape bucketing bounds the trace count to O(log(max_batch) *
        log(max_seq/block_size)) for paged decode plus O(log(max_batch))
        per prefill length bucket, but a cell first hit mid-traffic would
        stall the whole server behind XLA compilation — a serving engine
        warms them BEFORE taking load (the dummy inserts scribble on
        slot/scratch state, so never call this while streams are live).
        ``prompt_buckets`` lists prefill pad lengths to warm (snapped up
        into ``prefill_buckets``).  ``traffic`` — a
        ``cost_model.TrafficModel`` or an iterable of CellKeys — restricts
        compilation to the predicted-hit cells PLUS, always, the largest
        cell on each phase: the safe-fallback target the hot path bumps
        cold cells up to (see _run_paged_decode).  Compiled programs are
        per device, so each distinct cell is traced ONCE PER DEVICE, on the
        first server placed there, and the devices compile concurrently;
        cells already warm on a device are skipped, and the report sums
        compiled vs skipped traces over devices.  Pools / slot caches are
        still created on every server.  No-op unless batching."""
        if not self.batching:
            return PrecompileReport()
        hot = None
        if traffic is not None:
            hot = (set(traffic.hot_cells())
                   if hasattr(traffic, "hot_cells") else set(traffic))
        rows_ladder = self._row_buckets
        if self.paged:
            reachable_d = [(r, w) for r in rows_ladder
                           for w in self.width_buckets]
            fb_d = (rows_ladder[-1], self.width_buckets[-1])
        else:
            # masked-dense always runs the one full-shape trace
            reachable_d = [(self.max_batch, 0)]
            fb_d = reachable_d[0]
        plan_d = [c for c in reachable_d
                  if hot is None or c == fb_d
                  or (self._decode_kind, *c) in hot]
        buckets = sorted({bucket_up(b, self.prefill_buckets)
                          for b in prompt_buckets})
        reachable_p = [(r, b) for b in buckets for r in rows_ladder]
        fb_p = (rows_ladder[-1], buckets[-1]) if buckets else None
        plan_p = [c for c in reachable_p
                  if hot is None or c == fb_p
                  or (self._prefill_kind, *c) in hot]
        # migration gather/scatter cells: one per width bucket (the traces
        # are cheap — pure gather/scatter, no model math), so a mid-traffic
        # steal never stalls a server behind XLA compilation
        reachable_m = list(self.width_buckets) if self.paged else []
        fb_m = reachable_m[-1] if reachable_m else None
        plan_m = [w for w in reachable_m
                  if hot is None or w == fb_m
                  or (self._migrate_kind, w, self.kv_block_size) in hot]
        todo: dict = {}  # device -> (decode, prefill, migrate) cells
        reqs = []
        for si in self.pool.alive_servers():
            dev = self._devices[si]
            if dev in todo:  # device already planned: pools only
                d = p = m = []
            else:
                warm = self._warm_of(si)
                d = [c for c in plan_d if c not in warm.decode]
                p = [c for c in plan_p if c not in warm.prefill]
                m = [w for w in plan_m if w not in warm.migrate]
                todo[dev] = (d, p, m)
            reqs.append(self.pool.servers[si].submit(
                lambda si=si, d=d, p=p, m=m:
                    self._precompile_server(si, d, p, m),
                name=f"precompile-{si}"))
        for req in reqs:
            req.wait()
        for dev, (d, p, m) in todo.items():
            warm = self._warm.setdefault(dev, _WarmCells())
            warm.decode.update(d)
            warm.prefill.update(p)
            warm.migrate.update(m)
        reachable = len(reachable_d) + len(reachable_p) + len(reachable_m)
        compiled = sum(len(d) + len(p) + len(m)
                       for d, p, m in todo.values())

        def union(k, plan):
            return tuple(c for c in plan
                         if any(c in t[k] for t in todo.values()))

        return PrecompileReport(compiled=compiled,
                                skipped=reachable * len(todo) - compiled,
                                decode_cells=union(0, plan_d),
                                prefill_cells=union(1, plan_p),
                                migrate_cells=union(2, plan_m))

    def _precompile_server(self, si: int, decode_cells, prefill_cells,
                           migrate_cells=()):
        if self.paged:
            state = self._paged[si]
            if state.pools is None:
                state.pools = self._make_pools(si)
            params = self._params_on(si)
            for rows, w in decode_cells:
                # dummy batch: every row scatters token 0 at offset 0 of
                # the scratch block/slab (idempotent duplicates; the
                # scratch segment is never read)
                pack = np.zeros((rows, 4 + w), np.int32)
                pack[:, 2] = state.scratch_slab
                pack[:, 3] = state.scratch_seg
                pack[:, 4:] = state.scratch_block
                _, state.pools = jax.block_until_ready(
                    self._decode_paged(params, self._put(si, pack),
                                       state.pools))
            for w in migrate_cells:
                # round-trip the scratch resources through gather +
                # scatter: identical content lands back where it came from
                args = self._put(si, (
                    np.full((w,), state.scratch_block, np.int32),
                    np.int32(state.scratch_slab),
                    np.int32(state.scratch_seg)))
                packed = jax.block_until_ready(
                    self._export_kv(state.pools, *args))
                state.pools = jax.block_until_ready(
                    self._import_kv(state.pools, packed, *args))
        else:
            state = self._slots[si]
            if state.cache is None:
                state.cache = self._make_slot_cache(si)
            for _cell in decode_cells:
                toks, active = self._put(si, (
                    np.zeros((self.max_batch, 1), np.int32),
                    np.zeros((self.max_batch,), bool)))  # all-masked
                _, state.cache = jax.block_until_ready(
                    self._decode_masked(self._params_on(si), toks,
                                        state.cache, active))
        for rows, bucket in prefill_cells:
            batch = self._put(si, self._prefill_batch(
                np.zeros((rows, bucket), np.int32), np.ones((rows,))))
            logits, cache, _ = self._prefill(self._params_on(si), batch)
            jax.block_until_ready(
                self._last_token(logits, batch["lengths"]))
            if self.paged:
                state = self._paged[si]
                table = np.full((state.nb_max,), state.scratch_block,
                                np.int32)
                self._insert_slot_paged(si, cache, 0, table,
                                        state.scratch_slab,
                                        state.scratch_seg)
            else:
                self._insert_slot(si, 0, cache, 0)

    # -- generation ---------------------------------------------------------
    def generate(self, name: str, prompt: np.ndarray, *, steps: int,
                 greedy: bool = True) -> GenerationResult:
        """Run one job of stream ``name``: prefill + ``steps`` decode
        segments, each arbitrated by the stream's server.  The calling
        thread suspends between segments (never busy-waits).  While the
        engine is traced the call is one ``job`` span."""
        gen = (self._generate_batched if self.batching
               else self._generate_unbatched)
        rec = self.recorder
        if rec is None:
            return gen(name, prompt, steps=steps)
        job = rec.begin_job(prompt_len=int(prompt.shape[1]), steps=steps)
        try:
            return gen(name, prompt, steps=steps)
        finally:
            rec.end(job)

    def _generate_unbatched(self, name: str, prompt: np.ndarray, *,
                            steps: int) -> GenerationResult:
        rec = self.recorder
        job = rec.current_job() if rec is not None else 0
        spec = self._streams[name]
        prio = self.straggler.boost(name, spec.priority)
        res = GenerationResult()
        si = self.pool.server_of(name)
        params = self._params_on(si)
        batch = self._put(si, self._prefill_batch(prompt))

        seq_id = self._kv_reserve(name, prompt, steps)
        try:
            t0 = time.monotonic()
            req = self.pool.submit(
                name,
                lambda: jax.block_until_ready(self._prefill(params, batch)),
                priority=prio, name=f"{name}/prefill", job=job,
                phase="prefill")
            logits, cache, _ = req.wait()
            res.prefill_latency_s = time.monotonic() - t0
            self.straggler.observe(name, res.prefill_latency_s * 1e3)

            last = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
            res.first_token = int(last[0])
            res.first_token_at = time.monotonic()
            if rec is not None:
                rec.record("job.first_token", res.first_token_at,
                           res.first_token_at)
            for i in range(steps):
                step_batch = {"tokens": last[:, None]}
                t1 = time.monotonic()
                req = self.pool.submit(
                    name,
                    lambda sb=step_batch, c=cache: jax.block_until_ready(
                        self._decode(params, sb, c)),
                    priority=prio, name=f"{name}/decode{i}", job=job,
                    phase="decode")
                logits, cache, _ = req.wait()
                dt = time.monotonic() - t1
                res.decode_latencies_s.append(dt)
                self.straggler.observe(name, dt * 1e3)
                last = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
                res.tokens.append(int(last[0]))
        finally:
            self._kv_release(seq_id)
        return res

    def _generate_batched(self, name: str, prompt: np.ndarray, *,
                          steps: int) -> GenerationResult:
        """Continuous-batching path: length-bucketed batched prefill through
        the pool, insert into a slot (dense row) or the block pools (paged),
        then submit each decode step as a batchable request that the server
        coalesces — and, when paged, compacts — with other streams' steps.

        Stream recovery: when the stream's server dies mid-job
        (``ServerFailedError`` from any segment), the per-job _RecoveryLog
        holds the retained token prefix; after degraded-mode re-admission
        routes the stream to a survivor, the attempt re-prefills that prefix
        through the SAME bucketed prefill path and decoding resumes at the
        failed step — greedy tokens stay bit-identical to a failure-free
        run.  A stream shed by degraded admission raises StreamShedError."""
        if prompt.shape[0] != 1:
            raise ValueError("batched decode serves one sequence per stream "
                             f"job; got prompt batch {prompt.shape[0]}")
        if prompt.shape[1] + steps > self.max_seq:
            raise ValueError(f"prompt {prompt.shape[1]} + steps {steps} "
                             f"exceeds max_seq {self.max_seq}")
        res = GenerationResult()
        log = _RecoveryLog(prompt=np.asarray(prompt[0], np.int32))
        while True:
            si = self._await_server(name)
            try:
                self._attempt_batched(name, si, log, steps, res)
                return res
            except ServerFailedError:
                # the server declared itself dead (device loss / exhausted
                # transient retries) or the heartbeat monitor evicted it;
                # either way run recovery — idempotent if already handled —
                # then loop: re-admission has either moved us or shed us
                self._on_server_death(si)
                res.recoveries += 1

    def _await_server(self, name: str, timeout_s: float = 5.0) -> int:
        """The stream's current server index, waiting out an in-flight
        recovery (the evict happens before the re-assign, so a client can
        observe the gap); raises StreamShedError once the stream is shed or
        recovery never re-placed it."""
        deadline = time.monotonic() + timeout_s
        while True:
            if name in self._shed:
                raise StreamShedError(
                    f"stream {name!r} shed by degraded-mode admission")
            try:
                return self.pool.server_of(name)
            except KeyError:
                if time.monotonic() >= deadline:
                    raise StreamShedError(
                        f"stream {name!r} lost its server and was not "
                        "re-placed") from None
                time.sleep(0.001)

    def _attempt_batched(self, name: str, si: int, log: _RecoveryLog,
                         steps: int, res: GenerationResult) -> None:
        """One attempt on server ``si``: prefill the retained prefix, then
        decode until ``res`` holds ``steps`` tokens.  Owns its reservation
        and slot (released on ANY exit, so a failed attempt leaks nothing).

        Token accounting keeps recovery bit-identical: on the first attempt
        the prefill argmax is the decode-step-0 input (recorded, not
        appended); on a recovery attempt the prefix already CONTAINS that
        token, so the re-prefill's last-position argmax IS the failed step's
        output and is appended directly.  The reservation shrinks exactly in
        step: prefix_len + remaining_feeds == prompt_len + steps always."""
        spec = self._streams[name]
        prio = self.straggler.boost(name, spec.priority)
        prefix = log.retained_prefix()
        true_len = int(prefix.shape[0])
        append_first = log.first_token is not None
        feeds = steps - len(res.tokens) - (1 if append_first else 0)
        bucket = bucket_up(true_len, self.prefill_buckets)
        warm_prefill = self._warm_of(si).prefill
        if warm_prefill:
            # traffic-aware precompile warmed a subset of pad lengths:
            # steer to the smallest warm bucket that fits rather than cold-
            # compiling the tight one (padding tokens' KV lands in owned
            # blocks; per-row true lengths mask them out of attention)
            warm = sorted({b for _r, b in warm_prefill if b >= true_len})
            if warm:
                bucket = warm[0]

        # every submit is pinned to server object ``si`` — NOT routed by
        # stream name — so if a concurrent recovery re-binds this stream
        # mid-attempt, the next segment hits the DEAD server and raises
        # ServerFailedError instead of silently running against the new
        # server's pools with this attempt's (old-server) block table
        server = self.pool.servers[si]
        seq_id = table = None
        slab = seg = 0
        if self.paged:
            seq_id, table, slab, seg = self._paged_reserve(
                si, name, true_len, feeds, bucket)
        else:
            seq_id = self._kv_reserve(name, prefix[None, :], feeds)
        rec = self.recorder
        job = rec.current_job() if rec is not None else 0
        # while traced: the open job.turnaround span, set once the job is
        # in its decode phase (and counted in its server's ready counter)
        turn = None
        try:
            if rec is not None:
                wait = rec.begin("job.slot_wait")
            t_wait = time.monotonic()
            slot = self._acquire_slot(si)
            res.slot_wait_s += time.monotonic() - t_wait
            if rec is not None:
                rec.end(wait)
            self._active_jobs[name] = si
            try:
                t0 = time.monotonic()
                req = server.submit_batch(
                    (prefix, true_len),
                    run_batch=self._run_prefill_batch(si, bucket),
                    batch_key=("prefill", si, bucket), priority=prio,
                    name=f"{name}/prefill", job=job, phase="prefill")
                token, cache, src_row = req.wait()
                if self.paged:
                    server.submit(
                        lambda: self._insert_slot_paged(
                            si, cache, src_row, table, slab, seg),
                        priority=prio, name=f"{name}/insert", job=job,
                        phase="insert").wait()
                else:
                    server.submit(
                        lambda: self._insert_slot(
                            si, slot, cache, src_row),
                        priority=prio, name=f"{name}/insert", job=job,
                        phase="insert").wait()
                if rec is not None:
                    rec.add(server.name + ".ready")
                    turn = rec.begin("job.turnaround")
                res.prefill_latency_s = time.monotonic() - t0
                self.straggler.observe(name, res.prefill_latency_s * 1e3)

                if append_first:  # recovery attempt: resume point reached
                    res.resumed_at_monotonic.append(time.monotonic())
                    res.tokens.append(token)
                    log.generated.append(token)
                else:
                    log.first_token = res.first_token = token
                    res.first_token_at = time.monotonic()
                    if rec is not None:
                        rec.record("job.first_token", res.first_token_at,
                                   res.first_token_at)
                length = true_len
                run_batch = (self._run_paged_decode(si) if self.paged
                             else self._run_decode_batch(si))
                i = 0
                while len(res.tokens) < steps:
                    if name in self._shed:
                        raise StreamShedError(
                            f"stream {name!r} shed by degraded-mode "
                            "admission")
                    if self.paged:
                        # planned migration (steal / consolidate / drain):
                        # the stream's own thread moves its blocks at this
                        # step boundary — no decode of this stream can be
                        # in flight, so the copy sees a quiescent sequence
                        dst = self.pool.pending_migration(name)
                        if (dst is not None and dst != si
                                and dst in self.pool.alive_servers()):
                            dst_slot = self._try_acquire_slot(dst)
                            if dst_slot is None:
                                # destination full right now: abandon the
                                # steal rather than block holding our slot
                                self.pool.cancel_migration(name)
                            else:
                                try:
                                    table, slab, seg = (
                                        self._execute_migration(
                                            name, seq_id, si, dst, prio))
                                except OutOfBlocksError:
                                    self._release_slot(dst, dst_slot)
                                    self.pool.cancel_migration(name)
                                except BaseException:
                                    self._release_slot(dst, dst_slot)
                                    raise
                                else:
                                    self._release_slot(si, slot)
                                    if turn is not None:
                                        rec.add(server.name + ".ready", -1)
                                    slot, si = dst_slot, dst
                                    server = self.pool.servers[si]
                                    if turn is not None:
                                        rec.add(server.name + ".ready")
                                    run_batch = self._run_paged_decode(si)
                                    self._active_jobs[name] = si
                                    self.pool.complete_migration(name)
                    payload = ((token, table, length, slab, seg)
                               if self.paged else (slot, token))
                    if turn is not None:
                        rec.end(turn)
                    t1 = time.monotonic()
                    req = server.submit_batch(
                        payload, run_batch=run_batch,
                        batch_key=("decode", si), priority=prio,
                        name=f"{name}/decode{i}", job=job, phase="decode")
                    token = req.wait()  # this row's next token id
                    if turn is not None:
                        turn = rec.begin("job.turnaround")
                    dt = time.monotonic() - t1
                    res.decode_latencies_s.append(dt)
                    self.straggler.observe(name, dt * 1e3)
                    length += 1
                    res.tokens.append(token)
                    log.generated.append(token)
                    i += 1
            finally:
                if turn is not None:
                    rec.end(turn)
                    rec.add(server.name + ".ready", -1)
                self._active_jobs.pop(name, None)
                self._release_slot(si, slot)
        finally:
            if self.paged:
                self._paged_release(si, seq_id)
            else:
                self._kv_release(seq_id)

    # -- shared helpers -----------------------------------------------------
    def _prefill_batch(self, prompt: np.ndarray, lengths=None) -> dict:
        """Host-side prefill inputs (the caller places them on a device):
        ``lengths`` are per-row true lengths of a bucket-padded batch."""
        b = prompt.shape[0]
        batch = {"tokens": np.asarray(prompt, np.int32)}
        if lengths is not None:
            batch["lengths"] = np.asarray(lengths, np.int32)
        if self.cfg.family == "encdec":
            batch["frames"] = np.zeros(
                (b, self.cfg.encoder_seq, self.cfg.d_model),
                jnp.dtype(self.cfg.dtype))
        return batch

    def _kv_reserve(self, name: str, prompt: np.ndarray, steps: int):
        if self.kv is None:
            return None
        with self._kv_lock:
            self._seq_counter += 1
            seq_id = f"{name}#{self._seq_counter}"
            # reserve prompt + all decode tokens up front (reject early
            # rather than stall mid-generation)
            self.kv.allocate(seq_id, prompt.shape[1])
            try:
                self.kv.extend(seq_id, steps)
            except Exception:
                self.kv.free_seq(seq_id)
                raise
        self._held.setdefault(name, set()).add((None, seq_id))
        return seq_id

    def _kv_release(self, seq_id) -> None:
        if seq_id is not None:
            held = self._held.get(seq_id.rsplit("#", 1)[0])
            if held is not None:
                held.discard((None, seq_id))
            with self._kv_lock:
                self.kv.free_seq(seq_id, missing_ok=True)

    # -- fault tolerance ----------------------------------------------------
    def enable_fault_tolerance(self, *, heartbeat_timeout_s: float = 0.5,
                               poll_s: float = 0.02, max_retries: int = 2,
                               retry_backoff_s: float = 0.005,
                               watchdog: bool = False) -> "ServeEngine":
        """Switch on failure detection + stream recovery.

        Wires the pool's HeartbeatMonitor (servers beat between device
        calls, so a call outlasting ``heartbeat_timeout_s`` is a stall —
        the monitor thread evicts the server from outside, making the
        timeout per-device-call), sets each server's transient-error retry
        budget, optionally attaches a StepTimeWatchdog, and installs
        ``_on_server_death`` as the pool's death handler so eviction flows
        into degraded-mode re-admission instead of blind re-routing.
        Returns self for chaining."""
        self._ft_params = {"max_retries": max_retries,
                           "retry_backoff_s": retry_backoff_s,
                           "watchdog": watchdog}
        for s in self.pool.servers:
            s.max_retries = max_retries
            s.retry_backoff_s = retry_backoff_s
            if watchdog and s.watchdog is None:
                s.watchdog = StepTimeWatchdog()
        self.pool.enable_failure_detection(
            timeout=heartbeat_timeout_s, poll=poll_s,
            on_death=self._on_server_death)
        return self

    def _on_server_death(self, si: int, displaced=None) -> None:
        """Single recovery entry point, reached from the heartbeat monitor
        (stall), a server's own failure callback (device loss), or a client
        thread that caught ServerFailedError.  Serialized and idempotent:
        whichever caller evicts the server runs degraded-mode re-admission;
        everyone else returns once it is done.

        Surviving displaced streams are re-bound to the device degraded
        admission proved them on (with their priced recovery segment);
        unfitting streams are shed in reverse-priority order and their
        generator threads observe ``_shed`` at the next segment boundary."""
        with self._recovery_lock:
            if displaced is None:
                displaced = self.pool.evict_server(si, reroute=False)
            if displaced is None:
                return  # another caller already recovered this server
            # migration race window: a stream whose admission slot already
            # moved to its steal destination (admission.migrate committed,
            # pool binding not yet flipped) was displaced here but will NOT
            # be re-placed by evict_device — re-bind it to its live
            # admission placement instead of dropping it
            for s in list(displaced):
                d = self.admission.placement.get(s)
                if d is not None and d != si and self.admission.alive[d]:
                    task = next(t for t in self.admission.devices[d].streams
                                if t.name == s)
                    self.pool.reassign(s, d, utilization=task.G / task.T,
                                       priority=task.priority)
                    displaced.pop(s)
            report = self.admission.evict_device(
                si, recovery_cost_ms=self._recovery_cost_ms)
            for s, d in report.moved.items():
                task = next(t for t in self.admission.devices[d].streams
                            if t.name == s)
                self.pool.reassign(s, d, utilization=task.G / task.T,
                                   priority=task.priority)
            for s in report.shed:
                self._shed.add(s)
            self.degraded_reports.append(report)

    def _recovery_cost_ms(self, task: Task) -> float:
        """Price a stream's recovery segment — the re-prefill of its
        retained prefix on the surviving device.  Declared worst case is
        the stream's own prefill cost; a fitted cost model caps it at the
        predicted cost of the largest prefill bucket (never upward,
        mirroring calibrated admission's min())."""
        spec = self._streams.get(task.name)
        declared = (spec.prefill_ms if spec is not None
                    else task.segments[0].total)
        if self.cost_model is not None:
            pred = self.cost_model.predict(self._prefill_kind, 1,
                                           self.prefill_buckets[-1])
            if math.isfinite(pred):
                pred_ms = pred * getattr(self.cost_model, "safety", 1.0) * 1e3
                declared = min(declared, pred_ms) if declared > 0 else pred_ms
        return float(declared)

    # -- work stealing / consolidation / elastic scale ---------------------
    def _migration_cost_ms(self, name: str) -> float:
        """Price a steal of ``name``: gather + scatter of a full-width
        block table (worst case — the mover pays for every lane whether
        live or scratch-padded) at the cost model's measured "migrate"
        cell, with the calibration safety margin.  0 when uncalibrated or
        unmeasured — the depth-gap rule decides instead."""
        if not self.paged or self.cost_model is None:
            return 0.0
        w = bucket_up(self._paged[0].nb_max, self.width_buckets)
        pred = self.cost_model.predict(self._migrate_kind, w,
                                       self.kv_block_size)
        if not math.isfinite(pred):
            return 0.0
        return 2.0 * pred * getattr(self.cost_model, "safety", 1.0) * 1e3

    def _steal_profitable(self, name: str, depth_src: int, depth_dst: int,
                          mc_ms: float, min_gain_ms: float) -> bool:
        """Steal only when predicted queueing relief beats the move's cost:
        the victim's remaining decode steps each save the difference
        between a depth_src-row and a (depth_dst+1)-row batched decode
        step.  Without a cost model (or an unmeasured decode phase), fall
        back to the depth-gap >= 2 rule — stealing across a 1-deep gap just
        thrashes."""
        if self.cost_model is None:
            return depth_src - depth_dst >= 2
        spec = self._streams.get(name)
        if spec is None:
            return False
        w = self.width_buckets[-1] if self.width_buckets else 0
        c_src = self.cost_model.predict(
            self._decode_kind, bucket_up(depth_src, self._row_buckets), w)
        c_dst = self.cost_model.predict(
            self._decode_kind, bucket_up(depth_dst + 1, self._row_buckets),
            w)
        if not (math.isfinite(c_src) and math.isfinite(c_dst)):
            return depth_src - depth_dst >= 2
        gain_ms = spec.decode_steps * max(0.0, c_src - c_dst) * 1e3
        return gain_ms - mc_ms >= min_gain_ms

    def rebalance_once(self, *, min_gain_ms: float | None = None) -> int:
        """One work-stealing pass: move queued-behind streams from the
        deepest server onto the shallowest until the depth gap closes or
        no move is profitable.  Returns the number of steals REQUESTED —
        each victim's own thread performs the block copy at its next
        decode-step boundary (see _attempt_batched), so depth accounting
        here counts pending migrations at their destination to avoid
        over-stealing while copies are in flight.

        Runs on the heartbeat tick (or the fallback timer thread) and
        yields to recovery: if ``_recovery_lock`` is held the pass is
        skipped — rebalancing mid-eviction would race degraded-mode
        re-admission."""
        if min_gain_ms is None:
            min_gain_ms = self._steal_min_gain_ms
        if not self._recovery_lock.acquire(blocking=False):
            return 0
        try:
            stolen = 0
            draining = self.pool.draining()
            live = [i for i in self.pool.alive_servers()
                    if i not in draining]
            if len(live) < 2:
                return 0
            while True:
                depths = {i: 0 for i in live}
                for nm, si in list(self._active_jobs.items()):
                    if si not in depths:
                        continue
                    pd = self.pool.pending_migration(nm)
                    depths[pd if pd in depths else si] += 1
                src = max(depths, key=lambda i: (depths[i], i))
                dst = min(depths, key=lambda i: (depths[i], -i))
                if depths[src] - depths[dst] < 2:
                    return stolen
                victims = sorted(
                    (nm for nm, si in list(self._active_jobs.items())
                     if si == src and nm in self._streams
                     and nm not in self._shed
                     and self.pool.pending_migration(nm) is None),
                    key=lambda nm: self._streams[nm].priority)
                moved_one = False
                for victim in victims:
                    mc = self._migration_cost_ms(victim)
                    if not self._steal_profitable(victim, depths[src],
                                                  depths[dst], mc,
                                                  min_gain_ms):
                        continue
                    decision, d = self.admission.migrate(
                        victim, dst, migration_cost_ms=mc)
                    if d < 0:
                        continue
                    if not self.pool.request_migration(victim, dst):
                        # stream vanished / destination became illegal
                        # between the admission move and the intent: put
                        # the admission slot back (best-effort — if the
                        # stream is gone this is a no-op too)
                        self.admission.migrate(victim, src)
                        continue
                    stolen += 1
                    moved_one = True
                    break
                if not moved_one:
                    return stolen
        finally:
            self._recovery_lock.release()

    def enable_work_stealing(self, *, interval_s: float = 0.05,
                             min_gain_ms: float = 0.0) -> "ServeEngine":
        """Switch on periodic rebalancing.  Piggybacks on the heartbeat
        monitor's tick when fault tolerance is enabled (one thread, one
        cadence, same teardown guarantees); otherwise runs a dedicated
        daemon timer at ``interval_s``.  ``min_gain_ms`` is the minimum
        predicted net win (queueing relief minus migration cost) before a
        steal fires.  Returns self for chaining."""
        self._steal_min_gain_ms = float(min_gain_ms)

        def tick() -> None:
            try:
                self.rebalance_once()
            except Exception:
                pass  # best-effort: never kill the timer/monitor thread

        if self.pool._monitor is not None:
            self.pool._monitor.on_tick = tick
            return self
        stop = threading.Event()
        self._steal_stop = stop

        def loop() -> None:
            while not stop.wait(interval_s):
                tick()

        threading.Thread(target=loop, daemon=True,
                         name="steal-rebalance").start()
        return self

    def consolidate(self, si: int) -> dict[str, int]:
        """Drain server ``si`` by moving every stream it owns elsewhere:
        streams with a job in flight get a migration intent (their own
        thread moves the blocks at the next step boundary); idle streams
        are re-bound directly (nothing to copy — their next job prefills
        on the new server).  Each move is re-proven by admission first; a
        stream no destination can prove STAYS PUT and keeps running on the
        draining server (consolidation is an optimization, never a shed).
        Returns {stream: destination}.  ``remove_server`` completes the
        retirement once the server is empty."""
        self.pool.begin_drain(si)
        draining = self.pool.draining()
        dests = sorted((d for d in self.pool.alive_servers()
                        if d != si and d not in draining),
                       key=self.admission.gpu_utilization)
        moved: dict[str, int] = {}
        for name in self.pool.streams_on(si):
            active = self._active_jobs.get(name) == si
            mc = self._migration_cost_ms(name) if active else 0.0
            got = -1
            for d in dests:
                _, got = self.admission.migrate(name, d,
                                                migration_cost_ms=mc)
                if got >= 0:
                    break
            if got < 0:
                continue
            if active:
                self.pool.request_migration(name, got)
            else:
                task = next(t for t in self.admission.devices[got].streams
                            if t.name == name)
                self.pool.reassign(name, got, utilization=task.G / task.T,
                                   priority=task.priority)
            moved[name] = got
            dests.sort(key=self.admission.gpu_utilization)
        return moved

    def add_server(self) -> int:
        """Elastic scale-up: grow the pool AND the admission partition by
        one device mid-traffic; returns the new server index.  The server
        is placed on ``jax.local_devices()[si % n]``, inherits the pool's
        fault-tolerance settings (retry budget, watchdog, heartbeat wiring
        — the pool handles the monitor), gets its own slot/paged state, and
        warms its pools on its own thread.  On a device no earlier server
        used it first compiles the cells warm on server 0's device, so a
        freshly-joined server serves its first request at full speed."""
        with self._recovery_lock:
            si = self.pool.add_server()
            self._devices.append(self._device_for(si))
            di = self.admission.add_device()
            if si != di:
                raise RuntimeError(
                    f"pool/admission index drift: server {si} vs device "
                    f"{di}")
            if self.batching:
                self._slots.append(_SlotState(self.max_batch))
            if self.paged:
                self._paged.append(_PagedState(
                    self.cfg, self._num_blocks, self.kv_block_size,
                    self.max_batch, self.max_seq, family=self._family,
                    num_slabs=self._num_slabs,
                    num_segments=self._num_segments))
            s = self.pool.servers[si]
            s.set_recorder(self.recorder)
            if self._ft_params is not None:
                s.max_retries = self._ft_params["max_retries"]
                s.retry_backoff_s = self._ft_params["retry_backoff_s"]
                if self._ft_params["watchdog"] and s.watchdog is None:
                    s.watchdog = StepTimeWatchdog()
        warm = self._warm_of(si)
        plan = _WarmCells()
        if not (warm.decode or warm.prefill or warm.migrate):
            src = self._warm.get(self._devices[0], _WarmCells())
            plan = _WarmCells(set(src.decode), set(src.prefill),
                              set(src.migrate))
        s.submit(lambda: self._precompile_server(
                     si, sorted(plan.decode), sorted(plan.prefill),
                     sorted(plan.migrate)),
                 name=f"precompile-{si}").wait()
        warm.decode.update(plan.decode)
        warm.prefill.update(plan.prefill)
        warm.migrate.update(plan.migrate)
        return si

    def remove_server(self, si: int, *, timeout_s: float = 10.0) -> None:
        """Elastic scale-down: drain server ``si``, migrate its streams to
        proven destinations (live-KV migration for in-flight streams, a
        plain re-bind for idle ones), shed what the shrunk pool cannot
        prove, wait for the server to empty, and retire it.  Unlike
        ``consolidate`` this is a COMMITTED shrink — admission re-proves
        the whole placement via ``drain_device`` (identical machinery to
        failure eviction, priced as a cheap block copy instead of a
        re-prefill) and appends the resulting DegradedReport.  Raises
        TimeoutError if in-flight work does not clear in ``timeout_s``."""
        with self._recovery_lock:
            self.pool.begin_drain(si)
            report = self.admission.drain_device(
                si, migration_cost_ms=lambda t: self._migration_cost_ms(
                    t.name))
            for s, d in report.moved.items():
                if self._active_jobs.get(s) == si:
                    self.pool.request_migration(s, d)
                else:
                    task = next(t for t in self.admission.devices[d].streams
                                if t.name == s)
                    self.pool.reassign(s, d, utilization=task.G / task.T,
                                       priority=task.priority)
            for s in report.shed:
                self._shed.add(s)
                self.pool.remove(s)
            self.degraded_reports.append(report)
        deadline = time.monotonic() + timeout_s
        while (any(d == si for d in self._active_jobs.values())
               or self.pool.streams_on(si)):
            if time.monotonic() > deadline:
                raise TimeoutError(
                    f"server {si} did not drain within {timeout_s}s")
            time.sleep(0.005)
        self.pool.retire_server(si)

    def kv_usage(self) -> dict:
        """Per-kind pooled-cache occupancy across every manager —
        {"blocks", "slabs", "segments"} — excluding each paged server's
        permanently-held scratch resources.  Every count must return to
        zero once all streams drain (the per-family leak probe)."""
        usage = {"blocks": self.kv.blocks_in_use if self.kv is not None
                 else 0, "slabs": 0, "segments": 0}
        if self.paged:
            for st in self._paged:
                scratch = st.mgr.seqs.get("__scratch__")
                sb = len(scratch.blocks) if scratch is not None else 0
                ss = 1 if scratch is not None and scratch.slab is not None \
                    else 0
                sg = (1 if scratch is not None
                      and scratch.segment is not None else 0)
                usage["blocks"] += st.mgr.blocks_in_use - sb
                usage["slabs"] += st.mgr.slabs_in_use - ss
                usage["segments"] += st.mgr.segments_in_use - sg
        return usage

    def kv_blocks_in_use(self) -> int:
        """Total pooled-cache resources (blocks + slabs + segments) held
        across every manager, scratch excluded — i.e. the count that must
        return to zero once all streams drain (the chaos suite's leak
        check; see kv_usage() for the per-kind breakdown)."""
        return sum(self.kv_usage().values())

    def close(self) -> None:
        if self._steal_stop is not None:
            self._steal_stop.set()
        self.pool.shutdown()


def _cache_batch_axes(cfg, max_seq: int):
    """Per-leaf batch axis of the decode cache, discovered by diffing the
    shapes of a 1-row and a 2-row cache (family-agnostic: stacked layer
    leaves are (L,B,...), unstacked ones (B,...))."""
    c1 = jax.eval_shape(lambda: M.init_cache(cfg, 1, max_seq))
    c2 = jax.eval_shape(lambda: M.init_cache(cfg, 2, max_seq))

    def axis(a, b):
        for i, (da, db) in enumerate(zip(a.shape, b.shape)):
            if da != db:
                return i
        raise ValueError(f"no batch axis found in cache leaf {a.shape}")

    return jax.tree.map(axis, c1, c2)
