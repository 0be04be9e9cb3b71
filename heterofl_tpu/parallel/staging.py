"""Staged placement + zero-resharding steady-state dispatch.

The round engines (round_engine.py masked, grouped.py rate-grouped) are
"one XLA program per round" designs, but until this layer existed the HOST
still paid a per-round tax that eroded exactly the concurrency they exist
for: the per-user data stacks were re-wrapped with ``jnp.asarray`` every
round (an implicit reshard/upload whenever the committed sharding did not
match the program's specs), ``level_placement='slices'`` re-broadcast the
global params and re-resharded the replicated data into every level's
sub-mesh on every call, slot-id packing reallocated identical layouts, and
metric sums were fetched synchronously before the next round could
dispatch (ADVICE r5 item 3).

Four pieces remove that tax:

* :class:`PlacementCache` -- commits operands to their final mesh placement
  ONCE, keyed by the static ``(lo, hi)`` clients-axis device-row range of
  the target sub-mesh (``None`` = the full mesh).  Steady-state rounds then
  pass device-resident, correctly-sharded buffers straight into the jitted
  programs: no implicit per-call resharding, no host bytes moved.  Every
  placement is an EXPLICIT ``jax.device_put``, so the round path stays
  clean under ``jax.transfer_guard_host_to_device("disallow")`` -- the
  regression oracle in tests/test_staging.py.
* :class:`SlotPacker` -- cached host-side slot-layout buffers: packing the
  active-client ids into padded slot arrays reuses one preallocated buffer
  per static layout key instead of reallocating every round.
* :class:`PendingMetrics` / :class:`MetricsPipeline` -- per-round metric
  sums stay ON DEVICE; the pipeline fetches them in batches of
  ``fetch_every`` rounds (default 1 = reference parity), so round ``t+1``
  dispatches while round ``t``'s sums transfer, and ``flush()`` drains at
  eval boundaries (and before the driver exits).
* :class:`PhaseTimer` -- wall-clock sample/stage/dispatch/fetch breakdown,
  threaded into the fed drivers' per-round info line (and read by
  ``benchmark/``'s host-phase metrics), so placement regressions show up as a phase shift instead of
  an undifferentiated slowdown.

Streaming population staging (ISSUE 6) adds the input-side twins:

* :class:`ClientStore` -- the federation's user population as an
  O(1)-per-user METADATA index over the raw dataset arrays (per-user sample
  index rows or contiguous spans, per-user label sets), never densified
  into ``[num_users, ...]`` stacks.  Only the sampled cohort's shards are
  materialised, so host and device memory scale with ``active_clients``
  instead of the population -- "millions of users" becomes a config value.
* :class:`CohortStager` -- the double-buffered ``device_put`` pipeline:
  superstep N+1's cohort packs into a ring of :class:`SlotPacker` host
  buffers and commits to the mesh (explicit ``device_put`` + jitted private
  copy) while superstep N's scanned program computes.  A ring slot is
  refilled only after its previous private COPY is ready -- the copy severs
  any ``device_put`` host-buffer aliasing, so buffer reuse can never
  corrupt an in-flight superstep (same hazard :meth:`PlacementCache.put`
  documents, solved by pipelining instead of a per-call defensive copy).
* :class:`StagedCohort` -- one superstep's committed cohort (slot schedule
  + data stacks as scan xs) plus the static layout facts the dispatching
  engine needs; built by the engines' ``stage_cohort`` and consumed by
  ``train_superstep(..., cohort=...)``.
"""

from __future__ import annotations

import time
from contextlib import contextmanager
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..obs import spans as _spans


def commit_global(x, sharding: NamedSharding):
    """Commit a host (or single-device) value onto ``sharding`` on a mesh
    that may span multiple processes (ISSUE 17).

    ``jax.device_put`` can only target addressable devices; on a
    multi-controller mesh the committed array must be assembled from every
    process's local shards instead.  Each process calls this with the SAME
    host value (staging inputs are computed identically everywhere -- the
    single-controller-per-process GSPMD contract) and contributes the
    shards its devices own via ``jax.make_array_from_callback``.  On a
    fully-addressable (single-process) mesh this is exactly the explicit
    ``device_put`` the transfer guard blesses, so the steady-state path is
    unchanged."""
    if sharding.is_fully_addressable:
        return jax.device_put(x, sharding)
    if isinstance(x, jax.Array) and not x.is_fully_addressable:
        # already a global array on this multi-process runtime: an explicit
        # jitted reshard (a collective program; all processes call this in
        # lockstep at staging boundaries)
        fn = _RESHARDERS.get(sharding)
        if fn is None:
            # staticcheck: allow(jit-needs-donation): staging-boundary
            # reshard copy; the source stays live with the caller
            fn = jax.jit(lambda t: t + 0, out_shardings=sharding)
            _RESHARDERS[sharding] = fn
        return fn(x)
    # staticcheck: allow(no-asarray): multi-process staging commit -- the
    # callback below hands device_put-equivalent host slices to the runtime
    x = np.asarray(x)
    return jax.make_array_from_callback(x.shape, sharding, lambda idx: x[idx])


_GATHERERS: Dict[Any, Any] = {}
_RESHARDERS: Dict[Any, Any] = {}


def host_fetch(a):
    """Host copy of a committed array that may not be fully addressable
    (multi-process meshes, ISSUE 17).

    Fully-addressable arrays (every single-process mesh) take the plain
    ``np.asarray`` D2H path.  A fully-replicated multi-process array reads
    its local replica.  A SHARDED multi-process array is first reshard-
    gathered to replicated by a jitted identity with explicit
    ``out_shardings`` -- a collective program, so every process must call
    this in lockstep (the metric-fetch and checkpoint boundaries both do)."""
    if not isinstance(a, jax.Array) or a.is_fully_addressable:
        # staticcheck: allow(no-asarray): checkpoint/metric-boundary D2H
        return np.asarray(a)
    if a.is_fully_replicated:
        # staticcheck: allow(no-asarray): local-replica read, no collective
        return np.asarray(a.addressable_data(0))
    mesh = a.sharding.mesh
    fn = _GATHERERS.get(mesh)
    if fn is None:
        # staticcheck: allow(jit-needs-donation): checkpoint-boundary gather
        # copy; donating would free the caller's live carry/metric buffer
        fn = jax.jit(lambda t: t + 0, out_shardings=NamedSharding(mesh, P()))
        _GATHERERS[mesh] = fn
    # staticcheck: allow(no-asarray): replicated local-replica read
    return np.asarray(fn(a).addressable_data(0))


class PlacementCache:
    """Once-per-experiment placement of operands onto a mesh or its slices.

    Entries are keyed by ``(name, srange)`` -- ``srange`` is the static
    ``(lo, hi)`` clients-axis row range of a sub-mesh (``None`` = the full
    mesh) -- and invalidated only when the *identity* of the source arrays
    changes (a restage).  The cache holds references to both sources and
    committed outputs, so the ``id()`` keys stay valid for its lifetime.
    """

    def __init__(self, mesh: Mesh):
        self.mesh = mesh
        self._submeshes: Dict[Tuple[int, int], Mesh] = {}
        self._placed: Dict[Any, Tuple[Tuple[int, ...], Any, Any]] = {}
        self._scalars: Dict[Any, Any] = {}
        self._broadcasters: Dict[Any, Any] = {}

    def submesh(self, lo: int, hi: int) -> Mesh:
        """The cached sub-mesh over clients-axis device rows ``[lo, hi)``."""
        key = (lo, hi)
        if key not in self._submeshes:
            self._submeshes[key] = Mesh(self.mesh.devices[lo:hi], self.mesh.axis_names)
        return self._submeshes[key]

    def mesh_for(self, srange: Optional[Tuple[int, int]]) -> Mesh:
        return self.mesh if srange is None else self.submesh(*srange)

    def replicated(self, name: str, arrays: Sequence[Any],
                   srange: Optional[Tuple[int, int]] = None,
                   spec: P = P()) -> Tuple[Any, ...]:
        """Commit ``arrays`` onto the (sub-)mesh with ``spec`` exactly once.

        Steady-state calls with the same source arrays return the committed
        buffers without touching the host or the interconnect.
        """
        key = (name, srange, spec)
        src = tuple(id(a) for a in arrays)
        hit = self._placed.get(key)
        if hit is not None and hit[0] == src:
            return hit[2]
        sh = NamedSharding(self.mesh_for(srange), spec)
        out = tuple(commit_global(a, sh) for a in arrays)
        self._placed[key] = (src, tuple(arrays), out)
        return out

    def scalar(self, value, srange: Optional[Tuple[int, int]] = None,
               dtype=np.float32):
        """A device scalar cached by value (LR repeats for whole schedule
        plateaus; re-putting it every round is an avoidable transfer).

        One slot per (srange, dtype), replaced on a new value: per-round
        schedules (cosine/exponential) would otherwise grow the cache -- and
        leak device buffers -- for the experiment's lifetime."""
        slot = (srange, np.dtype(dtype).name)
        hit = self._scalars.get(slot)
        # staticcheck: allow(no-float-coercion): THE blessed scalar staging
        # path -- host value compare + one explicit put
        if hit is None or hit[0] != float(value):
            arr = commit_global(np.asarray(value, dtype),  # staticcheck: allow(no-asarray): explicit staging put
                                NamedSharding(self.mesh_for(srange), P()))
            self._scalars[slot] = (float(value), arr)  # staticcheck: allow(no-float-coercion): host cache key
            return arr
        return hit[1]

    def commit(self, tree, srange: Optional[Tuple[int, int]] = None,
               spec: P = P()):
        """Ensure every leaf is COMMITTED to the (sub-)mesh with ``spec``;
        already-committed leaves pass through untouched.

        The round programs' params argument needs this: ``model.init``
        returns uncommitted single-device arrays, so without it the first
        dispatch specialises the program on the uncommitted layout and the
        steady state pays a SECOND full compile when the round outputs come
        back mesh-committed -- one silent extra flagship compile (~40s) per
        experiment, caught by the staticcheck recompile-hazard audit.  Like
        :meth:`put`, the output may alias a device source's shards: only
        donate it where the source is consumed by contract (the params
        donation)."""
        sh = NamedSharding(self.mesh_for(srange), spec)

        def one(a):
            if getattr(a, "sharding", None) == sh and getattr(a, "committed", False):
                return a
            return commit_global(a, sh)

        return jax.tree_util.tree_map(one, tree)

    def put(self, tree, srange: Optional[Tuple[int, int]] = None,
            spec: P = P()):
        """Uncached EXPLICIT placement for per-round values (slot ids, level
        partials moving back to the full mesh).  Device-resident sources
        move over the interconnect only; host sources are explicit H2D,
        which the transfer guard permits (it exists to catch *implicit*
        moves).

        Numpy leaves are privately copied first: ``device_put`` may
        ZERO-COPY-ALIAS an aligned host buffer for the device array's whole
        lifetime (measured on CPU for replicated puts), so handing it a
        caller-owned buffer that gets refilled next round -- the SlotPacker
        contract -- would corrupt in-flight rounds once dispatch is
        pipelined.  The copy is tiny (slot-id vectors) and makes buffer
        reuse unconditionally safe.  NOTE: the result may likewise alias a
        DEVICE source's shards (observed even with ``may_alias=False``) --
        never donate it; use :meth:`broadcast` for donation-safe copies."""
        tree = jax.tree_util.tree_map(
            lambda a: a.copy() if isinstance(a, np.ndarray) else a, tree)
        sh = NamedSharding(self.mesh_for(srange), spec)
        return jax.tree_util.tree_map(lambda a: commit_global(a, sh), tree)

    def broadcast(self, tree, srange: Optional[Tuple[int, int]] = None):
        """Jitted replicate-copy onto the (sub-)mesh: private buffers that a
        downstream program can DONATE.

        ``device_put`` reuses the source buffer as a shard whenever the
        target mesh contains the source's device, so donating its output
        deletes the source array out from under the caller (measured on
        XLA:CPU; ``may_alias=False`` does not prevent it).  A jitted
        ``x + 0`` with explicit ``out_shardings`` always materialises fresh
        buffers, and as a compiled program it dispatches asynchronously --
        the broadcast overlaps with other levels' work."""
        fn = self._broadcasters.get(srange)
        sh = NamedSharding(self.mesh_for(srange), P())
        if fn is None:
            # staticcheck: allow(jit-needs-donation): the whole point of this
            # jit is to MATERIALISE fresh buffers the downstream program can
            # donate -- donating its input would re-alias the source
            fn = jax.jit(lambda t: jax.tree_util.tree_map(lambda a: a + 0, t),
                         out_shardings=sh)
            self._broadcasters[srange] = fn
        # two steps: the explicit put moves the data onto the (sub-)mesh (a
        # source committed to a SUPERSET of devices cannot enter the smaller
        # jit), then the jitted copy severs any buffer aliasing
        return fn(jax.tree_util.tree_map(lambda a: commit_global(a, sh), tree))

    def memo(self, name: str, sources: Sequence[Any], build: Callable[[], Any]):
        """Generic staged-computation cache (pad-and-commit paths in the
        evaluator): ``build()`` runs once per distinct source identity."""
        key = ("memo", name)
        src = tuple(id(s) for s in sources)
        hit = self._placed.get(key)
        if hit is not None and hit[0] == src:
            return hit[2]
        val = build()
        self._placed[key] = (src, tuple(sources), val)
        return val


class SlotPacker:
    """Cached host-side slot packing.

    ``buffer(key, shape)`` returns a preallocated buffer (int32 filled with
    -1, the padding-slot id, by default); callers write the active ids in
    place.  The per-round numpy packing previously reallocated identical
    layouts whenever the active-client count repeated -- with a fixed
    ``frac`` that is every round.  ``fill=None`` skips the fill for buffers
    whose every row is overwritten (the streaming cohort data stacks).
    """

    def __init__(self):
        self._bufs: Dict[Any, np.ndarray] = {}

    def buffer(self, key, shape: Tuple[int, ...], dtype=np.int32,
               fill=-1) -> np.ndarray:
        shape = tuple(shape)
        buf = self._bufs.get(key)
        if buf is None or buf.shape != shape or buf.dtype != np.dtype(dtype):
            buf = np.empty(shape, dtype)
            self._bufs[key] = buf
        if fill is not None:
            buf.fill(fill)
        return buf


class PhaseTimer:
    """Wall-clock phase accounting for the round path.

    Phases are free-form names; the engines use ``stage`` (host packing +
    placement-cache lookups), ``dispatch`` (program calls returning) and
    ``fetch`` (D2H metric assembly -- where the host waits for the device);
    the driver adds ``sample`` (the host cohort draw, ISSUE 11 -- its own
    phase so the O(population) -> O(active) sampler win is visible per
    round instead of hiding inside ``stage``).  The benchmark
    reads ``sample`` + ``dispatch`` as ``host_ms.round`` and ``stage`` as
    ``stage_ms.round``; device time has no phase here -- it is split by the
    program's own scopes (``obs.trace.SCOPES``).  Cheap enough to leave
    always on.

    ``trace`` (ISSUE 10): attach an :class:`~..obs.trace.TraceRecorder`
    and every finished phase is ALSO filed as a complete event on the
    run's Chrome-trace timeline -- the phase table and the trace share one
    measurement (and one clock: ``perf_counter``).
    """

    def __init__(self):
        self.totals: Dict[str, float] = {}
        self.calls: Dict[str, int] = {}
        self.trace = None  # optional obs.trace.TraceRecorder

    @contextmanager
    def phase(self, name: str):
        # staticcheck: allow(no-wallclock): host-side phase accounting -- the
        # timer never runs under trace (it wraps dispatch, not computation)
        t0 = time.perf_counter()
        # the thread's stack of open spans (obs/spans.py): what compiles
        # inside this phase names it as its parent
        frame = _spans.Open(name, t0, self)
        open_spans = _spans.RECORD.stack()
        open_spans.append(frame)
        try:
            yield
        finally:
            dt = time.perf_counter() - t0  # staticcheck: allow(no-wallclock): host-side phase accounting
            open_spans.pop()
            self.totals[name] = self.totals.get(name, 0.0) + dt
            self.calls[name] = self.calls.get(name, 0) + 1
            if frame.id is not None:
                # something compiled inside: the phase is a span of the
                # record, filed to the hook with its id
                _spans.RECORD.closed(frame, dt, open_spans)
            elif self.trace is not None:
                self.trace.complete(name, t0, dt, cat="phase")

    def snapshot(self) -> Dict[str, float]:
        return dict(self.totals)

    def delta(self, since: Dict[str, float]) -> Dict[str, float]:
        """Per-round breakdown: totals accumulated since ``since``."""
        return {k: v - since.get(k, 0.0) for k, v in self.totals.items()
                if v - since.get(k, 0.0) > 0.0}

    def amortized(self, since: Dict[str, float], rounds: int) -> Dict[str, float]:
        """Per-ROUND breakdown of a K-round superstep: the phase time
        accumulated since ``since`` divided by the rounds it paid for.  One
        stage+dispatch+fetch cycle serves all K rounds of a superstep, so
        this is the honest per-round host cost to compare against
        ``superstep_rounds=1`` (the ISSUE 2 acceptance metric)."""
        rounds = max(1, int(rounds))
        return {k: v / rounds for k, v in self.delta(since).items()}

    def summary(self, ndigits: int = 4) -> Dict[str, float]:
        return {k: round(v, ndigits) for k, v in sorted(self.totals.items())}


class PendingMetrics:
    """Per-round metric sums left on device; ``fetch()`` materialises them
    on the host (D2H) once and caches the result.  ``assemble`` maps the
    fetched tree to the caller-facing dict (the grouped engine packs
    per-level slot vectors back into active-client order)."""

    def __init__(self, device_tree, assemble: Optional[Callable[[Any], Any]] = None):
        self._tree = device_tree
        self._assemble = assemble
        self._host = None

    def fetch(self):
        if self._host is None:
            host = jax.tree_util.tree_map(host_fetch, self._tree)
            self._host = self._assemble(host) if self._assemble is not None else host
            self._tree = None  # release the device refs
        return self._host


class MetricsPipeline:
    """Deferred metric fetch: round ``t+1`` dispatches while round ``t``'s
    sums transfer.

    ``push`` returns the (tag, host_metrics) pairs that became due --
    everything pending once ``fetch_every`` rounds have accumulated
    (``fetch_every=1``, the default, degenerates to synchronous fetch =
    reference parity).  ``flush()`` drains unconditionally; call it at any
    boundary that must observe every round's metrics (the fed drivers flush
    at eval boundaries and before exit)."""

    def __init__(self, fetch_every: int = 1):
        self.fetch_every = max(1, int(fetch_every or 1))
        self._pending: List[Tuple[Any, PendingMetrics]] = []

    def push(self, tag, pending: PendingMetrics) -> List[Tuple[Any, Any]]:
        self._pending.append((tag, pending))
        if len(self._pending) >= self.fetch_every:
            return self.flush()
        return []

    def flush(self) -> List[Tuple[Any, Any]]:
        out = [(tag, p.fetch()) for tag, p in self._pending]
        self._pending = []
        return out

    def __len__(self) -> int:
        return len(self._pending)


# ---------------------------------------------------------------------------
# Streaming population staging (ISSUE 6)
# ---------------------------------------------------------------------------

def _idx64(a) -> np.ndarray:
    """Host index/label-metadata normalization for the ClientStore: a host
    int64 coercion that never wraps a device array -- cohort bytes reach the
    mesh only through the CohortStager's explicit device_put (hence the
    inline allow below)."""
    return np.asarray(a, np.int64)  # staticcheck: allow(no-asarray): host metadata only


class ClientStore:
    """The population as an O(1)-per-user metadata index; cohort shards
    materialise on demand.

    Holds references to the RAW dataset arrays (images/targets or batchified
    token rows) plus per-user index metadata in one of two layouts:

    * **CSR** (:meth:`from_split`): the driver's ``data_split`` index lists
      flattened into one int64 array with per-user offsets -- O(total
      samples) metadata, exactly what the split dict already holds, minus
      the per-user Python-list overhead.
    * **spans** (:meth:`from_spans`): per-user ``(start, size)`` contiguous
      ranges into the raw arrays -- O(num_users) metadata, the layout the
      million-user synthetic populations use (users window onto a shared
      sample pool; ``data/partition.span_population`` builds one).

    ``fill_*`` gather the SAMPLED users' shards into caller buffers with
    byte-identical layout to the eager ``data.pipeline.stack_client_shards``
    rows (same repeat-first-items padding, same sample masks, same label
    masks), so a streamed cohort reproduces the eager round bit for bit.
    Padding slots (user id -1) materialise user 0's shard -- the engines'
    ``maximum(uid, 0)`` convention -- so padded-slot local training stays
    finite exactly like the eager path; its results never reach aggregation
    or metrics (masked by ``valid``).
    """

    def __init__(self, data, target, sizes, classes_size, *, starts=None,
                 offsets=None, idx=None, label_offsets=None, label_idx=None,
                 kind="vision"):
        self.kind = kind
        self.data = np.ascontiguousarray(data)
        self.target = None if target is None else np.ascontiguousarray(target)
        self.sizes = _idx64(sizes)
        self.classes_size = int(classes_size)
        self._starts = None if starts is None else _idx64(starts)
        self._off = None if offsets is None else _idx64(offsets)
        self._idx = None if idx is None else _idx64(idx)
        self._loff = None if label_offsets is None else _idx64(label_offsets)
        self._lidx = None if label_idx is None else _idx64(label_idx)
        if (self._starts is None) == (self._off is None):
            raise ValueError("ClientStore needs exactly one of spans or CSR index")
        if self.sizes.size == 0 or (self.sizes <= 0).any():
            raise ValueError("every user needs a non-empty shard")
        self.num_users = int(self.sizes.size)
        self.shard_max = int(self.sizes.max())
        if kind == "lm" and (self.sizes != self.shard_max).any():
            raise ValueError("per-user row counts must match")  # stack parity

    # -- constructors --------------------------------------------------

    @classmethod
    def from_split(cls, data, target, data_split: Dict[int, Sequence[int]],
                   label_split, classes_size: int, kind: str = "vision"
                   ) -> "ClientStore":
        """Build from the driver's per-user index-list dicts (the eager
        stack's inputs)."""
        users = len(data_split)
        rows = [_idx64(data_split[u]) for u in range(users)]
        sizes = _idx64([r.size for r in rows])
        offsets = np.concatenate([[0], np.cumsum(sizes)])
        idx = np.concatenate(rows) if rows else np.zeros(0, np.int64)
        loff = lidx = None
        if label_split is not None:
            lrows = [_idx64(label_split[u]) for u in range(users)]
            loff = np.concatenate([[0], np.cumsum([r.size for r in lrows])])
            lidx = np.concatenate(lrows) if lrows else np.zeros(0, np.int64)
        return cls(data, target, sizes, classes_size, offsets=offsets, idx=idx,
                   label_offsets=loff, label_idx=lidx, kind=kind)

    @classmethod
    def from_spans(cls, data, target, starts, sizes, classes_size,
                   label_split=None, kind: str = "vision") -> "ClientStore":
        """Build from per-user contiguous ``(start, size)`` windows into the
        raw arrays: O(num_users) metadata, the million-user layout.
        ``label_split=None`` means every user sees every class (iid)."""
        starts = _idx64(starts)
        sizes = _idx64(sizes)
        if starts.shape != sizes.shape:
            raise ValueError(f"starts/sizes shape mismatch: {starts.shape} vs "
                             f"{sizes.shape}")
        if ((starts < 0) | (starts + sizes > len(data))).any():
            raise ValueError("a user span runs outside the raw data array")
        loff = lidx = None
        if label_split is not None:
            lrows = [_idx64(label_split[u]) for u in range(len(starts))]
            loff = np.concatenate([[0], np.cumsum([r.size for r in lrows])])
            lidx = np.concatenate(lrows) if lrows else np.zeros(0, np.int64)
        return cls(data, target, sizes, classes_size, starts=starts,
                   label_offsets=loff, label_idx=lidx, kind=kind)

    # -- metadata ------------------------------------------------------

    @property
    def metadata_nbytes(self) -> int:
        """Host bytes of the index metadata (the raw data pool is shared
        with the dataset and excluded): the O(active)-memory tests compare
        this against the eager ``[U, N, ...]`` stack it replaces."""
        return sum(a.nbytes for a in (self.sizes, self._starts, self._off,
                                      self._idx, self._loff, self._lidx)
                   if a is not None)

    @property
    def row_shape(self) -> Tuple[int, ...]:
        """Per-user shard shape at the store-wide static max: vision
        ``(shard_max,) + sample_shape``, LM ``(rows, row_len)``."""
        return (self.shard_max,) + self.data.shape[1:]

    def _row_idx(self, u: int, n: int) -> np.ndarray:
        """User ``u``'s padded sample-index row of length ``n`` -- the exact
        ``stack_client_shards`` rule: real indices first, then the first
        ``n - size`` indices repeated cyclically."""
        sz = int(self.sizes[u])
        j = np.arange(n)
        jj = np.where(j < sz, j, (j - sz) % sz)
        if self._starts is not None:
            return int(self._starts[u]) + jj
        lo = int(self._off[u])
        return self._idx[lo:lo + sz][jj]

    @staticmethod
    def _slot_user(u) -> int:
        # padding slots (-1) materialise user 0: the engines gather data at
        # maximum(uid, 0), so this is the eager stack's exact behaviour
        u = int(u)
        return u if u >= 0 else 0

    # -- cohort materialisation ----------------------------------------

    def fill_vision(self, user_ids, x_out: np.ndarray, y_out: np.ndarray,
                    m_out: np.ndarray) -> None:
        """Gather the given users' shards into ``[slots, shard_max, ...]``
        buffers (images, targets, sample masks)."""
        n = x_out.shape[1]
        ids = _idx64(user_ids).reshape(-1)
        for s, u in enumerate(ids):
            u = self._slot_user(u)
            idx = self._row_idx(u, n)
            x_out[s] = self.data[idx]
            y_out[s] = self.target[idx]
            sz = int(self.sizes[u])
            m_out[s, :sz] = 1.0
            m_out[s, sz:] = 0.0

    def fill_lm(self, user_ids, rows_out: np.ndarray) -> None:
        """Gather the given users' batchified token rows into
        ``[slots, rows, row_len]``."""
        ids = _idx64(user_ids).reshape(-1)
        for s, u in enumerate(ids):
            u = self._slot_user(u)
            rows_out[s] = self.data[self._row_idx(u, rows_out.shape[1])]

    def fill_labels(self, user_ids, lm_out: np.ndarray) -> None:
        """Per-user label-split masks ``[slots, classes]`` -- the streaming
        twin of ``data.pipeline.label_split_masks`` rows.  A store built
        without a label split (iid span populations) emits all-ones."""
        ids = _idx64(user_ids).reshape(-1)
        if self._lidx is None:
            lm_out[:] = 1.0
            return
        lm_out[:] = 0.0
        for s, u in enumerate(ids):
            u = self._slot_user(u)
            lm_out[s, self._lidx[self._loff[u]:self._loff[u + 1]]] = 1.0


class StagedCohort:
    """One superstep's committed cohort: the slot schedule + data stacks
    (device-resident, sharded over the cohort's slot axis, consumed as scan
    xs) plus the static layout facts that key the streaming program."""

    def __init__(self, engine: str, k: int, a: int, per_dev: int, sched,
                 data: Tuple, mode: Optional[str] = None,
                 positions: Optional[list] = None):
        self.engine = engine        # "masked" | "grouped"
        self.k = k                  # rounds in the superstep
        self.a = a                  # active clients per round
        self.per_dev = per_dev      # slots per device (per level, grouped)
        self.sched = sched          # device [k, ...] slot-id schedule
        self.data = data            # device cohort stacks, k-leading
        self.mode = mode            # grouped: "span" | "slices"
        self.positions = positions  # grouped: per-round per-level A-positions


class CohortStager:
    """Double-buffered cohort commit: host ring buffers -> explicit
    ``device_put`` -> jitted private copy.

    The pipeline contract: ``buffers()`` hands out one ring slot's host
    buffers to fill, ``commit()`` moves them to the mesh and returns PRIVATE
    device arrays.  ``device_put`` may zero-copy-alias an aligned host
    buffer for the device array's whole lifetime (the
    :meth:`PlacementCache.put` finding), so the committed arrays are a
    jitted replicate-copy of the put -- the copy dispatches asynchronously
    (it IS the overlap-able transfer) and its outputs share no buffers with
    the ring.  Before a ring slot is handed out again, ``buffers()`` blocks
    on that slot's previous COPY outputs: once the copy is ready its inputs
    are dead, so the refill can never corrupt an in-flight superstep -- and
    with prefetch depth 1 the wait lands two supersteps after the copy
    dispatched, i.e. it is effectively free.
    """

    def __init__(self, mesh: Mesh, depth: int = 1):
        self.mesh = mesh
        self.depth = max(1, int(depth))
        self._packer = SlotPacker()
        self._cursor: Dict[Any, int] = {}
        self._fences: Dict[Any, Any] = {}
        self._copiers: Dict[Any, Any] = {}

    def buffers(self, key, layouts: Sequence[Tuple]) -> Tuple[int, Tuple[np.ndarray, ...]]:
        """One ring slot's host buffers for ``layouts`` = [(shape, dtype,
        fill), ...]; returns ``(slot, buffers)``.  Blocks on the slot's
        previous private copy (see class docstring) before reuse."""
        slot = self._cursor.get(key, 0)
        fence = self._fences.pop((key, slot), None)
        if fence is not None:
            # staticcheck: allow(no-block-until-ready): the ring-slot fence
            # waits on the prior private COPY of these buffers (a memcpy that
            # finished supersteps ago), never on a round program
            jax.block_until_ready(fence)
        bufs = tuple(self._packer.buffer((key, slot, i), shape, dtype, fill)
                     for i, (shape, dtype, fill) in enumerate(layouts))
        return slot, bufs

    def _copier(self, sig, shardings):
        fn = self._copiers.get(sig)
        if fn is None:
            # staticcheck: allow(jit-needs-donation): the whole point of this
            # jit is to MATERIALISE private buffers severing any device_put
            # host aliasing -- donating its input would re-alias the ring
            fn = jax.jit(lambda t: tuple(a + 0 for a in t),
                         out_shardings=tuple(shardings))
            self._copiers[sig] = fn
        return fn

    def commit(self, key, slot: int, bufs: Sequence[np.ndarray],
               specs: Sequence[P]) -> Tuple:
        """Commit one ring slot's buffers to the mesh with ``specs`` and
        return the private device arrays; advances the ring cursor."""
        shardings = tuple(NamedSharding(self.mesh, s) for s in specs)
        put = tuple(commit_global(b, sh) for b, sh in zip(bufs, shardings))
        sig = tuple((b.shape, b.dtype.str, s) for b, s in zip(bufs, specs))
        out = self._copier(sig, shardings)(put)
        self._fences[(key, slot)] = out
        self._cursor[key] = (slot + 1) % (self.depth + 1)
        return out
