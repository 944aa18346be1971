"""Parameter-server path: sparse embedding tables for recommendation.

TPU-native re-design of the reference PS stack (reference:
paddle/fluid/distributed/ps/table/memory_sparse_table.h:39 (hash-grown
rows, per-slot optimizer rules sparse_sgd_rule.cc),
ps/service/ps_client.h:63 pull/push RPC, python
distributed/ps/the_one_ps.py:919 TheOnePSRuntime).

The reference splits the job into brpc KV servers + trainers doing async
pull/push, because GPU memory can't hold ads-scale vocabularies. The
TPU-native split is host-RAM vs HBM on the SAME machines:

- `MemorySparseTable` — in-process host KV (id → row), rows created on
  first touch (unbounded vocab), per-row optimizer state applied on push
  (SGD / AdaGrad rules, as the reference applies optimizers server-side).
  Single-process per table; multi-host id routing (reference `id % nproc`
  table sharding) is not implemented yet — in a multi-host job give each
  process its own table over a disjoint id space, or use
  `ShardedEmbedding`.
- `SparseEmbedding` — the `paddle.static.nn.sparse_embedding` analog: a
  layer that pulls the batch's unique rows to HBM, runs the dense lookup
  on device (tape-differentiable), and pushes row gradients back on
  backward via a gradient hook (async-push semantics). Eager-mode by
  design, like the reference's PS mode (the dense math still jits).
- `ShardedEmbedding` — the SPMD alternative when the vocab fits HBM:
  table row-sharded over a mesh axis; XLA inserts the gather/all-to-all
  (SparseCore-style path). Works inside DistributedTrainStep.
"""
import numpy as np

import jax
import jax.numpy as jnp

from ..tensor_core import Tensor
from . import mesh as mesh_mod

__all__ = ["SparseSGDRule", "SparseAdaGradRule", "SparseAdamRule",
           "MemorySparseTable", "SSDSparseTable", "ShardedSparseTable",
           "GeoSparseTable", "make_sparse_table", "resolve_rule",
           "SparseEmbedding", "ShardedEmbedding", "live_tables"]

# every SparseEmbedding registers here so fleet.stop_worker()/
# save_persistables can flush/save all live PS tables (the reference's
# server-side table registry, the_one_ps.py _get_tables). Weak refs:
# the registry must not keep dead embeddings' tables alive.
import weakref as _weakref

_LIVE_TABLES = []  # (name, weakref) pairs


def _register_table(table, name=None):
    for _, ref in _LIVE_TABLES:
        if ref() is table:
            return  # one table shared by several embeddings: register once
    name = name or f"sparse_table_{len(_LIVE_TABLES)}"
    _LIVE_TABLES.append((name, _weakref.ref(table)))


def live_tables():
    """(name, table) for every live registered table; dead refs pruned."""
    out = []
    alive = []
    for name, ref in _LIVE_TABLES:
        t = ref()
        if t is not None:
            out.append((name, t))
            alive.append((name, ref))
    _LIVE_TABLES[:] = alive
    return out


# ------------------------------------------------------ optimizer rules

class SparseSGDRule:
    """reference: ps/table/sparse_sgd_rule.cc naive rule."""

    slot_dim = 0

    def __init__(self, learning_rate=0.01):
        self.lr = learning_rate

    def slots_width(self, dim):
        return self.slot_dim

    def init_slots(self, n, dim):
        return np.zeros((n, 0), np.float32)

    def apply(self, rows, slots, grads):
        return rows - self.lr * grads, slots


class SparseAdaGradRule:
    """reference: sparse_adagrad rule — per-row accumulated g², applied
    server-side on push."""

    slot_dim = 1

    def __init__(self, learning_rate=0.05, initial_g2sum=0.0, eps=1e-8):
        self.lr = learning_rate
        self.g0 = initial_g2sum
        self.eps = eps

    def slots_width(self, dim):
        return self.slot_dim

    def init_slots(self, n, dim):
        return np.full((n, 1), self.g0, np.float32)

    def apply(self, rows, slots, grads):
        g2 = slots[:, 0] + (grads * grads).mean(axis=1)
        scale = self.lr / (np.sqrt(g2) + self.eps)
        return rows - scale[:, None] * grads, g2[:, None]


class SparseAdamRule:
    """reference: sparse_sgd_rule.cc SparseAdamSGDRule — per-element
    m/v moments plus a per-row step count, applied server-side on push.
    Slot layout [m(dim), v(dim), t] matches the native C++ core."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 eps=1e-8):
        self.lr = learning_rate
        self.beta1 = beta1
        self.beta2 = beta2
        self.eps = eps

    def slots_width(self, dim):
        return 2 * dim + 1

    def init_slots(self, n, dim):
        return np.zeros((n, 2 * dim + 1), np.float32)

    def apply(self, rows, slots, grads):
        dim = rows.shape[1]
        m, v, t = slots[:, :dim], slots[:, dim:2 * dim], slots[:, -1]
        t = t + 1.0
        m = self.beta1 * m + (1 - self.beta1) * grads
        v = self.beta2 * v + (1 - self.beta2) * grads * grads
        mhat = m / (1 - self.beta1 ** t[:, None])
        vhat = v / (1 - self.beta2 ** t[:, None])
        new_rows = rows - self.lr * mhat / (np.sqrt(vhat) + self.eps)
        return new_rows, np.concatenate([m, v, t[:, None]], axis=1)


def resolve_rule(rule):
    """Accept a rule object or its reference config name ('sgd'/'naive',
    'adagrad', 'adam'; reference sparse_sgd_rule.cc registers rules by
    name)."""
    if rule is None or not isinstance(rule, str):
        return rule
    names = {"sgd": SparseSGDRule, "naive": SparseSGDRule,
             "adagrad": SparseAdaGradRule,
             "std_adagrad": SparseAdaGradRule,
             "adam": SparseAdamRule}
    try:
        return names[rule]()
    except KeyError:
        raise ValueError(
            f"unknown sparse rule {rule!r}; one of {sorted(names)}"
        ) from None


# --------------------------------------------------------------- table

def make_sparse_table(embedding_dim, rule=None, initializer=None, seed=0,
                      backend="auto", path=None, accessor=None):
    """Table factory. backend="auto"/"native" uses the C++ core
    (paddle_tpu.native NativeSparseTable, mirroring the reference's C++
    memory_sparse_table) when available and the rule is a stock
    SGD/AdaGrad/Adam with no custom initializer; backend="ssd" (requires
    `path`) memmaps rows to disk (reference ssd_sparse_table.h);
    otherwise (or with backend="python") the numpy MemorySparseTable.
    accessor="ctr" tracks per-row show/click with decay-scored eviction
    (reference ctr_accessor.cc; memory/native engines only).
    All expose the same pull/push/len/state_dict contract."""
    rule = resolve_rule(rule)
    if path is not None and backend == "auto":
        backend = "ssd"  # an explicit path is a request for persistence
    if path is not None and backend not in ("ssd",):
        raise ValueError(
            f'`path` given but backend={backend!r} does not persist — '
            'use backend="ssd" (or "auto")')
    if backend == "ssd":
        if path is None:
            raise ValueError('backend="ssd" needs a directory `path`')
        if accessor is not None:
            raise ValueError(
                "accessor='ctr' is not supported on the SSD backend yet "
                "(show/click meta is not memmapped) — use memory/native")
        return SSDSparseTable(embedding_dim, path, rule=rule,
                              initializer=initializer, seed=seed)
    if backend in ("auto", "native"):
        from .. import native

        kind = None
        if rule is None or isinstance(rule, SparseAdaGradRule):
            kind = "adagrad"
        elif isinstance(rule, SparseAdamRule):
            kind = "adam"
        elif isinstance(rule, SparseSGDRule):
            kind = "sgd"
        usable = (kind is not None and initializer is None
                  and native.is_available())
        if usable:
            r = rule or SparseAdaGradRule()
            kw = dict(lr=r.lr, seed=seed, accessor=accessor)
            if kind == "adagrad":
                kw.update(g0=r.g0, eps=r.eps)
            elif kind == "adam":
                kw.update(beta1=r.beta1, beta2=r.beta2, eps=r.eps)
            return native.NativeSparseTable(embedding_dim, rule=kind, **kw)
        if backend == "native":
            raise RuntimeError(
                "native backend requested but unavailable (no g++) "
                "or incompatible with a custom rule/initializer")
    return MemorySparseTable(embedding_dim, rule=rule,
                             initializer=initializer, seed=seed,
                             accessor=accessor)


class MemorySparseTable:
    """Host-RAM KV table with create-on-first-touch rows (pure-python
    engine; see make_sparse_table for the native C++ alternative).
    accessor="ctr" tracks per-row (show, click, unseen) with
    `update_show_click` and decay-scored eviction via `shrink`
    (reference ps/table/ctr_accessor.cc)."""

    def __init__(self, embedding_dim, rule=None, initializer=None, seed=0,
                 accessor=None):
        if accessor not in (None, "ctr"):
            raise ValueError(f"accessor={accessor!r}: expected None/'ctr'")
        self.accessor = accessor
        self._meta = np.zeros((0, 3), np.float32)  # show, click, unseen
        self.dim = embedding_dim
        self.rule = resolve_rule(rule) or SparseAdaGradRule()
        self._rng = np.random.default_rng(seed)
        self._init = initializer or (
            lambda n: (self._rng.standard_normal((n, self.dim)) /
                       np.sqrt(self.dim)).astype(np.float32))
        # id-aware initializers (f(n, ids)) make row values a pure
        # function of the id — required for shard-count-independent
        # initialization (a sharded table must equal the 1-process one)
        import inspect

        try:
            self._init_takes_ids = (
                len(inspect.signature(self._init).parameters) >= 2)
        except (TypeError, ValueError):
            self._init_takes_ids = False
        self._rows = {}   # id -> row index in the arrays below
        self._data = np.zeros((0, self.dim), np.float32)
        self._slots = self.rule.init_slots(0, self.dim)

    def __len__(self):
        return len(self._rows)

    def _ensure(self, ids):
        # dedupe: a new id repeated within one batch must allocate ONE row
        missing = list(dict.fromkeys(
            int(i) for i in ids if int(i) not in self._rows))
        if missing:
            base = len(self._rows)
            for k, i in enumerate(missing):
                self._rows[i] = base + k
            new = (self._init(len(missing), np.asarray(missing, np.int64))
                   if self._init_takes_ids else self._init(len(missing)))
            self._append_rows(new,
                              self.rule.init_slots(len(missing), self.dim))
            if self.accessor:
                self._meta = np.concatenate(
                    [self._meta, np.zeros((len(missing), 3), np.float32)])

    def _append_rows(self, new_rows, new_slots):
        """Storage hook: append freshly-initialized rows (overridden by
        SSDSparseTable to write into the memmap)."""
        self._data = np.concatenate([self._data, new_rows])
        self._slots = np.concatenate([self._slots, new_slots])

    def _ordered_ids(self):
        """ids sorted by their row index (the on-disk/state-dict order)."""
        ids = np.fromiter(self._rows.keys(), np.int64, len(self._rows))
        order = np.argsort([self._rows[int(i)] for i in ids])
        return ids[order]

    def pull(self, ids):
        """ids: 1-D int array → (n, dim) float32 rows (reference
        PSClient::PullSparse)."""
        ids = np.asarray(ids).reshape(-1)
        self._ensure(ids)
        idx = np.fromiter((self._rows[int(i)] for i in ids), np.int64,
                          len(ids))
        if self.accessor:
            self._meta[idx, 2] = 0.0
        return self._data[idx]

    def push(self, ids, grads):
        """Apply the optimizer rule to the given rows (reference
        PSClient::PushSparse; dedup-accumulates repeated ids)."""
        ids = np.asarray(ids).reshape(-1)
        grads = np.asarray(grads, np.float32).reshape(len(ids), self.dim)
        uniq, inv = np.unique(ids, return_inverse=True)
        acc = np.zeros((len(uniq), self.dim), np.float32)
        np.add.at(acc, inv, grads)
        self._ensure(uniq)
        idx = np.fromiter((self._rows[int(i)] for i in uniq), np.int64,
                          len(uniq))
        new_rows, new_slots = self.rule.apply(
            self._data[idx], self._slots[idx], acc)
        self._data[idx] = new_rows
        self._slots[idx] = new_slots
        if self.accessor:
            self._meta[idx, 2] = 0.0

    def set_rows(self, ids, rows):
        """Overwrite row VALUES directly (no optimizer rule) — the geo
        trainer's base refresh and bulk loading path (reference
        memory_sparse_geo_table.h direct value install)."""
        ids = np.asarray(ids).reshape(-1)
        rows = np.asarray(rows, np.float32).reshape(len(ids), self.dim)
        self._ensure(ids)
        idx = np.fromiter((self._rows[int(i)] for i in ids), np.int64,
                          len(ids))
        # fancy-index assignment copies VALUES into the table's own
        # storage; `rows` itself is never retained
        self._data[idx] = rows  # ptlint: disable=PTL501

    # -- CTR accessor (reference ctr_accessor.cc) --
    def update_show_click(self, ids, shows, clicks):
        """Accumulate per-row show/click event counts."""
        if not self.accessor:
            raise RuntimeError("table created without accessor='ctr'")
        ids = np.asarray(ids).reshape(-1)
        shows = np.asarray(shows, np.float32).reshape(-1)
        clicks = np.asarray(clicks, np.float32).reshape(-1)
        if not len(ids) == len(shows) == len(clicks):
            raise ValueError("ids/shows/clicks length mismatch")
        self._ensure(ids)
        idx = np.fromiter((self._rows[int(i)] for i in ids), np.int64,
                          len(ids))
        np.add.at(self._meta[:, 0], idx, shows)
        np.add.at(self._meta[:, 1], idx, clicks)
        self._meta[idx, 2] = 0.0

    def shrink(self, decay=0.98, nonclk_coeff=0.1, delete_threshold=0.8,
               delete_after_unseen=7):
        """One maintenance round: decay show/click, age rows one round,
        evict rows scoring click + nonclk_coeff·(show − click) below
        delete_threshold once unseen longer than delete_after_unseen
        (reference Table::Shrink + ctr_accessor ShowClickScore).
        Returns the evicted row count."""
        if not self.accessor:
            raise RuntimeError("table created without accessor='ctr'")
        self._meta[:, 0] *= decay
        self._meta[:, 1] *= decay
        self._meta[:, 2] += 1.0
        show, click, unseen = (self._meta[:, 0], self._meta[:, 1],
                               self._meta[:, 2])
        score = click + nonclk_coeff * (show - click)
        drop = (score < delete_threshold) & (unseen > delete_after_unseen)
        if not drop.any():
            return 0
        keep = ~drop
        kept_ids = self._ordered_ids()[keep]  # row-index order
        self._data = self._data[keep]
        self._slots = self._slots[keep]
        self._meta = self._meta[keep]
        self._rows = {int(i): k for k, i in enumerate(kept_ids)}
        return int(drop.sum())

    # -- checkpoint integration (paddle_tpu.distributed.checkpoint) --
    def state_dict(self):
        sd = {"ids": self._ordered_ids(), "data": self._data,
              "slots": self._slots}
        if self.accessor:
            sd["meta"] = self._meta
        return sd

    def set_state_dict(self, sd):
        ids = np.asarray(sd["ids"]._value if isinstance(sd["ids"], Tensor)
                         else sd["ids"]).reshape(-1)
        self._rows = {int(i): k for k, i in enumerate(ids)}
        # np.array (not asarray): the table owns its storage — an
        # aliased state-dict buffer mutated by the caller after load
        # would silently corrupt rows (PTL501)
        self._data = np.array(
            sd["data"]._value if isinstance(sd["data"], Tensor)
            else sd["data"], np.float32)
        self._slots = np.array(
            sd["slots"]._value if isinstance(sd["slots"], Tensor)
            else sd["slots"], np.float32)
        if self.accessor:
            self._meta = (np.array(
                sd["meta"]._value if isinstance(sd.get("meta"), Tensor)
                else sd["meta"], np.float32) if "meta" in sd
                else np.zeros((len(ids), 3), np.float32))


class SSDSparseTable(MemorySparseTable):
    """Disk-backed sparse table: row values and optimizer slots live in
    memmap'd files under `path`, only the id→row index stays in RAM
    (reference: ps/table/ssd_sparse_table.h:39, which spills cold rows to
    RocksDB). The OS page cache plays the hot-row cache — recently
    touched pages stay resident, cold pages are evicted under memory
    pressure — so billion-row tables train on hosts whose RAM holds only
    the index. Same pull/push/state_dict contract as MemorySparseTable;
    call `flush()` (or rely on `save` in checkpointing) to persist, and
    reopening the same `path` restores the table.
    """

    _DATA, _SLOTS, _IDS, _META = "rows.f32", "slots.f32", "ids.npy", \
        "meta.json"

    def __init__(self, embedding_dim, path, rule=None, initializer=None,
                 seed=0, capacity=4096):
        import json
        import os

        super().__init__(embedding_dim, rule=rule, initializer=initializer,
                         seed=seed)
        self._path = path
        os.makedirs(path, exist_ok=True)
        # slots_width(dim): Adam's slot width depends on dim; plain
        # slot_dim attr kept as the fallback for custom rules
        self._slot_dim = (self.rule.slots_width(self.dim)
                          if hasattr(self.rule, "slots_width")
                          else self.rule.slot_dim)
        ids_f = os.path.join(path, self._IDS)
        if (not os.path.exists(ids_f)
                and os.path.exists(self._file(self._DATA))):
            raise ValueError(
                f"SSD table dir {path} has row data but no {self._IDS} "
                "(crash before flush?) — recover or clear the directory; "
                'refusing the destructive "w+" re-create')
        if os.path.exists(ids_f):
            # the flat files carry no shape info — validate against the
            # persisted meta or a dim typo reinterprets every row
            with open(self._file(self._META)) as f:
                meta = json.load(f)
            if (meta["dim"] != self.dim
                    or meta["slot_dim"] != self._slot_dim):
                raise ValueError(
                    f"SSD table at {path} was written with dim="
                    f"{meta['dim']}/slot_dim={meta['slot_dim']}, "
                    f"reopened with dim={self.dim}/slot_dim="
                    f"{self._slot_dim}")
            ids = np.load(ids_f)
            self._rows = {int(i): k for k, i in enumerate(ids)}
            self._cap = max(capacity, 1, len(ids))
            self._map(create=False)
        else:
            self._cap = max(capacity, 1)
            self._map(create=True)
        self._refresh_views(len(self._rows))

    # -- storage primitives ------------------------------------------------
    def _file(self, name):
        import os

        return os.path.join(self._path, name)

    def _map(self, create):
        mode = "w+" if create else "r+"
        self._data_mm = np.memmap(self._file(self._DATA), np.float32,
                                  mode=mode, shape=(self._cap, self.dim))
        if self._slot_dim:
            self._slots_mm = np.memmap(
                self._file(self._SLOTS), np.float32, mode=mode,
                shape=(self._cap, self._slot_dim))

    def _refresh_views(self, n):
        self._n = n
        self._data = self._data_mm[:n]
        self._slots = (self._slots_mm[:n] if self._slot_dim
                       else np.zeros((n, 0), np.float32))

    def _grow_to(self, need):
        cap = self._cap
        while cap < need:
            cap *= 2
        if cap == self._cap:
            return
        self._data_mm.flush()
        row_bytes = self.dim * 4
        with open(self._file(self._DATA), "r+b") as f:
            f.truncate(cap * row_bytes)
        if self._slot_dim:
            self._slots_mm.flush()
            with open(self._file(self._SLOTS), "r+b") as f:
                f.truncate(cap * self._slot_dim * 4)
        self._cap = cap
        self._map(create=False)

    # -- overridden storage hook ------------------------------------------
    def _append_rows(self, new_rows, new_slots):
        base = self._n
        need = base + len(new_rows)
        self._grow_to(need)
        self._data_mm[base:need] = new_rows
        if self._slot_dim:
            self._slots_mm[base:need] = new_slots
        self._refresh_views(need)

    # -- persistence -------------------------------------------------------
    def flush(self):
        import json

        np.save(self._file(self._IDS), self._ordered_ids())
        with open(self._file(self._META), "w") as f:
            json.dump({"dim": self.dim, "slot_dim": self._slot_dim}, f)
        self._data_mm.flush()
        if self._slot_dim:
            self._slots_mm.flush()

    def set_state_dict(self, sd):
        def _np_of(v):
            return np.asarray(v._value if isinstance(v, Tensor) else v)

        ids = _np_of(sd["ids"]).reshape(-1)
        data = _np_of(sd["data"]).astype(np.float32)
        self._grow_to(max(len(ids), 1))
        self._rows = {int(i): k for k, i in enumerate(ids)}
        self._data_mm[:len(ids)] = data
        if self._slot_dim:
            self._slots_mm[:len(ids)] = _np_of(
                sd["slots"]).astype(np.float32)
        self._refresh_views(len(ids))
        self.flush()


# ------------------------------------------------- multi-host sharding

class ShardedSparseTable:
    """Multi-process id-routed sparse table.

    The reference shards ids across PS server processes (`id % server_num`)
    with async trainer-side push queues (reference:
    ps/table/memory_sparse_table.h:39 shard layout,
    ps/service/brpc_ps_client.h:195 id-routed pull/push RPC,
    ps/service/communicator/communicator.h:427 AsyncCommunicator bounded
    push queues). TPU-native redesign: there are no separate server
    processes — every trainer process owns the shard `id % world == rank`
    of the table in host RAM next to its chip.

    Transport (reference brpc_ps_client.h:195's point-to-point RPC):
    requests and rows move PEER-TO-PEER over the jax.distributed
    coordination KV (`xproc.send_np/recv_np`) — each rank sends every
    owner exactly its own request ids and receives exactly its own rows,
    so wire traffic is O(batch·dim) per rank, independent of world size.
    (transport="gather" keeps the old object-all-gather path — O(world·
    batch) received per rank — for A/B and debugging.) Row assembly is
    vectorized: responses preserve request order, so per-owner rows
    scatter straight into the unique-row matrix, no python dict loop.

    Contract: pull/flush are collective — every process must call them
    the same number of times. SPMD data-parallel training guarantees this
    (DistributedBatchSampler pads every rank to the same batch count).

    Push is ASYNC with bounded staleness (AsyncCommunicator semantics):
    `push` only queues gradients locally; the queue is flushed — one
    routing round applying grads on their owner shards — every
    `staleness`-th push call (and on `flush()`). With staleness=1 pushes
    are synchronous and a sharded run is bit-identical to a 1-process
    table (asserted by tests/test_ps_deepfm.py).
    """

    _TAG_PULL_REQ, _TAG_PULL_ROWS = 151, 152
    _TAG_PUSH_IDS, _TAG_PUSH_GRADS = 153, 154
    _TAG_SC_IDS, _TAG_SC_CNT = 155, 156

    def __init__(self, embedding_dim, rule=None, initializer=None, seed=0,
                 staleness=1, backend="auto", world=None, rank=None,
                 path=None, transport="p2p", timeout_ms=600_000,
                 accessor=None):
        from . import xproc

        if world is None:
            world = jax.process_count() if xproc.is_multiprocess() else 1
        if rank is None:
            rank = jax.process_index() if world > 1 else 0
        self.world, self.rank = world, rank
        self.dim = embedding_dim
        self.staleness = max(1, int(staleness))
        if transport not in ("p2p", "gather"):
            raise ValueError(f"transport={transport!r}: p2p or gather")
        self.transport = transport
        # p2p recv deadline: must cover peer rank skew (first-step XLA
        # compiles, data stalls) — 10 min default, not xproc's 60 s
        self.timeout_ms = int(timeout_ms)
        if path is not None:
            # each shard owns its OWN directory — ranks sharing one
            # memmap file would overwrite each other's row layouts
            import os

            path = os.path.join(path, f"rank{rank}")
        self.local = make_sparse_table(embedding_dim, rule=rule,
                                       initializer=initializer, seed=seed,
                                       backend=backend, path=path,
                                       accessor=accessor)
        self._pending_ids = []
        self._pending_grads = []
        self._push_calls = 0
        import threading

        self._local_lock = threading.Lock()
        self._io_pool = None   # lazy persistent executor (pull hot path)

    def _io_executor(self):
        """Long-lived thread pool for per-peer serve/recv concurrency —
        spawning 2·world threads on every pull would rival the latency
        the concurrency hides."""
        if self._io_pool is None:
            from concurrent.futures import ThreadPoolExecutor

            self._io_pool = ThreadPoolExecutor(
                max_workers=max(2, 2 * (self.world - 1)),
                thread_name_prefix="ps-io")
        return self._io_pool

    def __len__(self):
        return len(self.local)

    def _gather_obj(self, obj):
        from . import xproc

        return xproc.all_gather_obj(obj, max_len=1 << 27)

    def _peers(self):
        return [r for r in range(self.world) if r != self.rank]

    def _exchange_by_owner(self, owner, arrays, tags):
        """Scatter row-aligned `arrays` (leading dim = rows, e.g. ids +
        their grads) to the rank owning each row, and return this rank's
        concatenated incoming set (own slice + one recv per peer). One
        tag per array; all sends are posted before any blocking recv.
        The shared spine of the p2p flush / update_show_click routing."""
        from . import xproc

        for r in self._peers():
            sel = owner == r
            for arr, tag in zip(arrays, tags):
                xproc.send_np(arr[sel], r, tag)
        parts = [[arr[owner == self.rank]] for arr in arrays]
        peers = self._peers()
        if peers:
            # per-peer recvs run CONCURRENTLY (arrival order across peers
            # is arbitrary; a sequential loop made latency linear in
            # world size — round-4 weak spot)
            def _recv_peer(r):
                return [xproc.recv_np(r, tag, timeout_ms=self.timeout_ms)
                        for tag in tags]

            for got in self._io_executor().map(_recv_peer, peers):
                for k, arr in enumerate(got):
                    parts[k].append(arr)
        return [np.concatenate(p) for p in parts]

    def pull(self, ids):
        """Route each id to its owner shard, receive the rows back."""
        ids = np.asarray(ids).reshape(-1).astype(np.int64)
        if self.world == 1:
            return self.local.pull(ids)
        uniq, inv = np.unique(ids, return_inverse=True)
        if self.transport == "gather":
            return self._pull_gather(ids, uniq, inv)
        from . import xproc

        owner = uniq % self.world
        rows = np.empty((len(uniq), self.dim), np.float32)
        # 1) every rank posts its request to each owner (non-blocking)
        for r in self._peers():
            xproc.send_np(uniq[owner == r], r, self._TAG_PULL_REQ)
        mine = owner == self.rank
        with self._local_lock:
            rows[mine] = self.local.pull(uniq[mine]) if mine.any() else 0
        # 2+3) serve each peer's request from the local shard AND collect
        # responses, all peers CONCURRENTLY — a slow peer no longer
        # stalls serving (or receiving from) the others; local table
        # access stays serialized under a lock (create-on-touch mutates)
        peers = self._peers()
        if peers:
            local_lock = self._local_lock

            def _serve(r):
                want = xproc.recv_np(r, self._TAG_PULL_REQ,
                                     timeout_ms=self.timeout_ms)
                with local_lock:
                    served = (self.local.pull(want) if len(want)
                              else np.zeros((0, self.dim), np.float32))
                # parameter rows must arrive bit-exact — the int8 wire
                # opt-in (PT_QUANT_ALLREDUCE) is for gradient-like
                # payloads, never the master copies being served
                xproc.send_np(served, r, self._TAG_PULL_ROWS,
                              quantize=False)

            def _recv(r):
                return xproc.recv_np(r, self._TAG_PULL_ROWS,
                                     timeout_ms=self.timeout_ms)

            ex = self._io_executor()
            serve_futs = [ex.submit(_serve, r) for r in peers]
            recv_futs = [ex.submit(_recv, r) for r in peers]
            try:
                resp = [f.result() for f in recv_futs]
                for f in serve_futs:
                    f.result()
            except Exception:
                # a dead peer must not leak queued work into the
                # fixed-size pool: cancel whatever hasn't started
                # (threads already blocked in recv will expire on their
                # own timeout)
                for f in serve_futs + recv_futs:
                    f.cancel()
                raise
            # responses preserve request order: scatter by owner mask
            for r, got in zip(peers, resp):
                rows[owner == r] = got
        return rows[inv] if len(ids) else \
            np.zeros((0, self.dim), np.float32)

    def _pull_gather(self, ids, uniq, inv):
        """Legacy all-gather transport (every rank sees every request)."""
        requests = self._gather_obj(uniq)          # round 1: who needs what
        served = {}
        for requester, want in enumerate(requests):
            mine = want[want % self.world == self.rank]
            if len(mine):
                served[requester] = (mine, self.local.pull(mine))
        responses = self._gather_obj(served)       # round 2: serve rows
        rows = np.empty((len(uniq), self.dim), np.float32)
        for owner_rank, resp in enumerate(responses):
            if self.rank in resp:
                sids, srows = resp[self.rank]
                # sids ⊂ uniq and both sorted: vectorized placement
                rows[np.searchsorted(uniq, sids)] = srows
        return rows[inv] if len(ids) else \
            np.zeros((0, self.dim), np.float32)

    def push(self, ids, grads):
        """Queue gradients; flush every `staleness`-th call."""
        ids = np.asarray(ids).reshape(-1).astype(np.int64)
        # np.array: grads are QUEUED until flush() — the training loop
        # reuses its gradient buffers every step, so an aliased view
        # here would flush later steps' values (PTL501)
        grads = np.array(grads, np.float32).reshape(len(ids), self.dim)
        self._pending_ids.append(ids)
        self._pending_grads.append(grads)
        # single-writer: push() runs only on the training-loop thread;
        # _local_lock guards the LOCAL table against the pull-serving
        # io-pool, which never touches the push-side staleness counter
        self._push_calls += 1  # ptlint: disable=PTL702
        if self._push_calls % self.staleness == 0:
            self.flush()

    def update_show_click(self, ids, shows, clicks):
        """Route show/click event counts to owner shards (collective,
        like flush; reference ctr_accessor statistics live server-side)."""
        ids = np.asarray(ids).reshape(-1).astype(np.int64)
        shows = np.asarray(shows, np.float32).reshape(-1)
        clicks = np.asarray(clicks, np.float32).reshape(-1)
        if not len(ids) == len(shows) == len(clicks):
            # validate BEFORE any send: a partial exchange would leave a
            # tag stream with an orphaned message and silently mis-pair
            # every later batch
            raise ValueError("ids/shows/clicks length mismatch")
        counts = np.stack([shows, clicks], axis=1)  # (n, 2) row-aligned
        if self.world == 1:
            self.local.update_show_click(ids, counts[:, 0], counts[:, 1])
            return
        cat_ids, cat_cnt = self._exchange_by_owner(
            ids % self.world, (ids, counts),
            (self._TAG_SC_IDS, self._TAG_SC_CNT))
        if len(cat_ids):
            self.local.update_show_click(cat_ids, cat_cnt[:, 0],
                                         cat_cnt[:, 1])

    def shrink(self, **kw):
        """Per-shard eviction round (collective: call on every rank).
        Returns this rank's evicted count."""
        return self.local.shrink(**kw)

    def flush(self):
        """Collective: route queued grads to owner shards and apply the
        optimizer rule there (server-side optimize, as in the reference)."""
        if self.world == 1:
            for i, g in zip(self._pending_ids, self._pending_grads):
                self.local.push(i, g)
            self._pending_ids, self._pending_grads = [], []
            return
        if self._pending_ids:
            ids = np.concatenate(self._pending_ids)
            grads = np.concatenate(self._pending_grads)
        else:
            ids = np.zeros((0,), np.int64)
            grads = np.zeros((0, self.dim), np.float32)
        self._pending_ids, self._pending_grads = [], []
        if self.transport == "gather":
            incoming = self._gather_obj((ids, grads))  # one routing round
            cat_ids = np.concatenate([i for i, _ in incoming])
            cat_grads = np.concatenate([g for _, g in incoming])
            mask = cat_ids % self.world == self.rank
            if mask.any():
                # local push dedup-accumulates repeated ids, so grads for
                # the same id from several trainers sum correctly
                self.local.push(cat_ids[mask], cat_grads[mask])
            return
        cat_ids, cat_grads = self._exchange_by_owner(
            ids % self.world, (ids, grads),
            (self._TAG_PUSH_IDS, self._TAG_PUSH_GRADS))
        if len(cat_ids):
            # ONE rule application per flush: dedup happens inside push
            self.local.push(cat_ids, cat_grads)

    # checkpoint: each rank persists its own shard (pairs with the
    # per-rank sharded checkpoint layout in distributed/checkpoint.py)
    def state_dict(self):
        return self.local.state_dict()

    def set_state_dict(self, sd):
        self.local.set_state_dict(sd)


# --------------------------------------------------------- layer shims

class SparseEmbedding:
    """PS-backed embedding lookup (reference static.nn.sparse_embedding /
    _pull_sparse ops). Pull unique rows → dense device lookup
    (differentiable) → push row grads on backward via hook.

    Overlap: `prefetch(next_ids)` starts the host-KV pull for the NEXT
    batch on a background thread while the chip computes the current
    step (the reference's AsyncCommunicator pull pipeline,
    communicator.h:427); the matching `__call__` consumes the prefetched
    rows without blocking on the table."""

    def __init__(self, embedding_dim, table=None, rule=None, name=None,
                 backend="auto", path=None):
        import threading

        self.table = table if table is not None else make_sparse_table(
            embedding_dim, rule=rule, backend=backend, path=path)
        _register_table(self.table, name)
        self.dim = embedding_dim
        self._pool = None
        self._pending = None  # (key, uniq, inv, shape, future)
        self._bound = None    # SparseTrainStep trace mode (rows, inv)
        # serializes background pulls against backward-hook pushes: the
        # table's row map/arrays are not safe under concurrent mutation
        self._table_lock = threading.Lock()

    def _decompose(self, ids):
        ids_np = np.asarray(
            ids._value if isinstance(ids, Tensor) else ids).astype(np.int64)
        uniq, inv = np.unique(ids_np.reshape(-1), return_inverse=True)
        return ids_np, uniq, inv

    @staticmethod
    def _key(ids_np):
        return (ids_np.shape, ids_np.tobytes())

    def prefetch(self, ids):
        """Start pulling `ids`'s rows in the background. The pull holds
        the table lock, so it serializes against the backward-hook push
        (bounded staleness: a prefetch reads the table state when the
        lock is acquired, as in the reference async PS). Collective
        tables (multi-host ShardedSparseTable) pull in the FOREGROUND —
        collectives issued from a side thread would interleave with the
        main thread's flush collectives and deadlock ranks."""
        import concurrent.futures

        ids_np, uniq, inv = self._decompose(ids)

        def locked_pull():
            with self._table_lock:
                return self.table.pull(uniq)

        if getattr(self.table, "world", 1) > 1:
            fut = concurrent.futures.Future()
            fut.set_result(locked_pull())
        else:
            if self._pool is None:
                self._pool = concurrent.futures.ThreadPoolExecutor(
                    max_workers=1, thread_name_prefix="ps-prefetch")
            fut = self._pool.submit(locked_pull)
        self._pending = (self._key(ids_np), uniq, inv, ids_np.shape, fut)
        return fut

    def _acquire(self, ids):
        """Pull-or-consume-prefetch: returns (ids_np, uniq, inv, rows_np).
        Shared by the eager __call__ and SparseTrainStep's host stage."""
        if self._pending is not None:
            key, p_uniq, p_inv, p_shape, fut = self._pending
            probe = np.asarray(
                ids._value if isinstance(ids, Tensor) else ids).astype(
                np.int64)
            if self._key(probe) == key:
                self._pending = None
                return probe, p_uniq, p_inv, fut.result()
        ids_np, uniq, inv = self._decompose(ids)
        with self._table_lock:
            rows_np = self.table.pull(uniq)
        return ids_np, uniq, inv, rows_np

    def __call__(self, ids):
        from ..ops._helpers import apply_jfn

        if self._bound is not None:
            # SparseTrainStep trace mode: rows/inv are jit ARGUMENTS —
            # no host pull, no hook (the step returns row grads to push)
            rows_b, inv_b = self._bound
            return apply_jfn(
                "sparse_embedding_lookup",
                lambda w, i: jnp.take(w, i, axis=0), rows_b, inv_b)
        ids_np, uniq, inv, rows_np = self._acquire(ids)
        rows = Tensor(jnp.asarray(rows_np), stop_gradient=False)
        table = self.table
        lock = self._table_lock

        def _push(g):
            with lock:
                table.push(uniq, np.asarray(
                    g._value if isinstance(g, Tensor) else g))
            return g

        rows.register_hook(_push)
        inv_t = Tensor(jnp.asarray(inv.reshape(ids_np.shape)),
                       stop_gradient=True)
        return apply_jfn(
            "sparse_embedding_lookup",
            lambda w, i: jnp.take(w, i, axis=0), rows, inv_t)

    def parameters(self):
        return []  # rows live in the table, optimized server-side


from ..jit import TrainStep as _TrainStepBase


def find_sparse_embeddings(obj, _seen=None):
    """Walk an object graph for SparseEmbedding instances (they are not
    Layers, so Layer traversal misses them)."""
    if _seen is None:
        _seen = set()
    if id(obj) in _seen:
        return []
    _seen.add(id(obj))
    if isinstance(obj, SparseEmbedding):
        return [obj]
    out = []
    d = getattr(obj, "__dict__", None)
    if isinstance(d, dict):
        for v in d.values():
            out += find_sparse_embeddings(v, _seen)
    if isinstance(obj, dict):  # Layer._sub_layers etc.
        for v in obj.values():
            out += find_sparse_embeddings(v, _seen)
    if isinstance(obj, (list, tuple)):
        for v in obj:
            out += find_sparse_embeddings(v, _seen)
    return out


class SparseTrainStep(_TrainStepBase):
    """Compiled PS training step (the throughput fix for eager PS
    models): per step, the HOST pulls each table's unique rows, then ONE
    donated XLA program runs forward + backward + the dense-param
    optimizer update AND returns the row gradients, which the host
    pushes back to the tables (server-side optimizer rules apply them).
    The eager per-op dispatch loop — reference async-PS's trainer shape,
    and this module's default — becomes three stages that pipeline with
    `prefetch` (issue it AFTER the step so the pending slot survives
    until the next step's pull).

    Subclasses jit.TrainStep: param/optimizer bookkeeping, donation, and
    the armed-profiler ips hook are shared; _build/__call__ differ
    because rows/inv are extra traced inputs and row grads an extra
    output. Unique-row counts vary per batch, so rows/inv are PADDED to
    a fixed capacity (ids.size worst case): one compile, stable shapes;
    padded rows are never referenced by inv and get exactly zero
    gradient.

    Constraints: every SparseEmbedding must key off the SAME ids tensor
    (`batch[ids_index]`, the single-table CTR layout); loss_fn must be
    jit-traceable (pure jnp/tape ops). Single-PROCESS: the dense update
    runs inside the compiled step with local grads, so multi-host
    data-parallel PS training keeps the eager loop (whose hook pushes
    and explicit dense all-reduce are collective-safe —
    tests/ps_worker.py phase B is the pattern).
    """

    def __init__(self, model, loss_fn, optimizer, ids_index=0,
                 donate_params=True):
        self.embs = find_sparse_embeddings(model)
        if not self.embs:
            raise ValueError("model has no SparseEmbedding tables; use "
                             "jit.TrainStep for dense models")
        super().__init__(model, loss_fn, optimizer,
                         donate_params=donate_params)
        self.ids_index = ids_index

    def lower(self, *batch):
        raise NotImplementedError(
            "SparseTrainStep's compiled signature carries per-step "
            "rows/inv operands; lower a dense TrainStep for memory "
            "analysis instead")

    def compile_stats(self, check_donation=False):
        if check_donation:
            # same reason lower() is unsupported: the donation probe
            # would re-lower with TrainStep's 7-arg layout against this
            # step's 9-arg signature
            raise NotImplementedError(
                "SparseTrainStep's compiled signature carries per-step "
                "rows/inv operands; run the donation probe on a dense "
                "TrainStep of the same model instead")
        return super().compile_stats()

    def _build(self):
        import jax

        from ..core import rng as rng_mod

        model, loss_fn, opt = self.model, self.loss_fn, self.optimizer
        param_objs, trainable, embs = (self._param_objs, self._trainable,
                                       self.embs)
        train_objs = [p for p, t in zip(param_objs, trainable) if t]
        # per-step dropout keys, as TrainStep — and like there, a runtime
        # ARGUMENT, not a closure constant: baked keys make per-instance
        # HLOs, so no two instances could share a compile-cache entry
        self._base_key = rng_mod.next_key()

        def pure_loss(train_vals, rows_vals, frozen_vals, inv_vals,
                      batch_vals, step_key):
            originals = [p._value for p in param_objs]
            it_t, it_f = iter(train_vals), iter(frozen_vals)
            for p, tr in zip(param_objs, trainable):
                p._value = next(it_t) if tr else next(it_f)
            try:
                for emb, rv, iv in zip(embs, rows_vals, inv_vals):
                    emb._bound = (Tensor(rv, stop_gradient=False),
                                  Tensor(iv, stop_gradient=True))
                batch = [Tensor(v, stop_gradient=True)
                         for v in batch_vals]
                with rng_mod.trace_key_scope(step_key):
                    loss = loss_fn(model, *batch)
                new_frozen = [p._value for p, tr in zip(param_objs,
                                                        trainable)
                              if not tr]
            finally:
                for emb in embs:
                    emb._bound = None
                for p, v in zip(param_objs, originals):
                    p._value = v
            return loss._value, new_frozen

        def step(train_vals, frozen_vals, opt_states, lr, rows_vals,
                 inv_vals, batch_vals, step_idx, base_key):
            step_key = jax.random.fold_in(base_key, step_idx)
            (loss, new_frozen), (dgrads, rgrads) = jax.value_and_grad(
                pure_loss, argnums=(0, 1), has_aux=True)(
                train_vals, rows_vals, frozen_vals, inv_vals, batch_vals,
                step_key)
            new_vals, new_states = opt.apply_gradients_tree(
                train_vals, dgrads, opt_states, lr, param_objs=train_objs)
            return loss, new_vals, new_states, new_frozen, rgrads

        self._compiled = jax.jit(step, donate_argnums=(0, 1, 2))

    def __call__(self, *batch):
        if self._compiled is None:
            self._build()
        ids = batch[self.ids_index]
        cap = int(np.prod(np.asarray(
            ids._value if isinstance(ids, Tensor) else ids).shape))
        rows_vals, inv_vals, uniqs, counts = [], [], [], []
        for emb in self.embs:
            ids_np, uniq, inv, rows_np = emb._acquire(ids)
            u = len(uniq)
            pad = np.zeros((cap - u, rows_np.shape[1]), rows_np.dtype)
            rows_vals.append(jnp.asarray(np.concatenate([rows_np, pad])))
            inv_vals.append(jnp.asarray(inv.reshape(ids_np.shape)))
            uniqs.append(uniq)
            counts.append(u)
        train_vals, frozen_vals = self._split_vals()
        if self._opt_states is None:
            self._opt_states = self.optimizer.init_states_tree(train_vals)
        batch_vals = [b._value if isinstance(b, Tensor) else jnp.asarray(b)
                      for b in batch]
        loss, new_vals, self._opt_states, new_frozen, rgrads = \
            self._compiled(train_vals, frozen_vals, self._opt_states,
                           np.float32(self.optimizer.get_lr()),
                           rows_vals, inv_vals,
                           batch_vals,
                           jnp.asarray(self.optimizer._step_count,
                                       jnp.uint32), self._base_key)
        it, it_f = iter(new_vals), iter(new_frozen)
        for p, t in zip(self._param_objs, self._trainable):
            p._value = next(it) if t else next(it_f)
        self.optimizer._step_count += 1
        for emb, uniq, u, g in zip(self.embs, uniqs, counts, rgrads):
            with emb._table_lock:
                emb.table.push(uniq, np.asarray(g)[:u])
        from ..profiler import benchmark

        bm = benchmark()
        if bm.enabled:  # armed ips meter, as jit.TrainStep
            n = batch_vals[0].shape[0] if batch_vals and \
                getattr(batch_vals[0], "ndim", 0) else None
            bm.auto_step(num_samples=n)
        return Tensor(loss, stop_gradient=True)


class GeoSparseTable:
    """Geo-async trainer-side sparse table (reference: GeoCommunicator,
    ps/service/communicator/communicator.h:598 — delta-accumulating
    trainer sync; ps/table/memory_sparse_geo_table.h:1 — the server
    merges pushed deltas into the authoritative rows).

    Semantics: every trainer owns a LOCAL working copy trained with the
    optimizer rule IMMEDIATELY (zero per-step routing for known ids).
    Every `sync_every`-th push runs one geo round:

      1. delta = local_row − base_row for every locally-dirty id,
      2. deltas route to their owner shard (id % world) and MERGE by
         summation into the authoritative table,
      3. the trainer refreshes: merged rows are pulled back, installed
         as the new local values AND the new base.

    Staleness is bounded by `sync_every` pushes; with sync_every=1 and
    one trainer this degenerates to a plain local table. pull()s of ids
    this trainer has never seen fetch the authoritative base first (one
    collective round per step, empty-request safe — the reference's
    sparse init pull). All pull/push calls are COLLECTIVE, like
    ShardedSparseTable: data-parallel lockstep guarantees matching call
    counts.
    """

    def __init__(self, embedding_dim, rule=None, initializer=None,
                 seed=0, sync_every=8, world=None, rank=None,
                 timeout_ms=600_000, refresh_chunk=4096):
        from . import xproc

        if world is None:
            world = jax.process_count() if xproc.is_multiprocess() else 1
        if rank is None:
            rank = jax.process_index() if world > 1 else 0
        self.world, self.rank = world, rank
        self.dim = embedding_dim
        self.sync_every = max(1, int(sync_every))
        # the geo delta algebra needs local create-on-touch to agree
        # with the authority's initial value WITHOUT a network round:
        # the initializer must be a pure function of the id (the
        # reference geo tables initialize deterministically too)
        if initializer is None:
            raise ValueError(
                "GeoSparseTable needs an id-deterministic initializer "
                "(rows are created locally AND on the authority shard; "
                "order-dependent random init would corrupt deltas)")
        self._init_fn = initializer
        self.refresh_chunk = max(1, int(refresh_chunk))
        self.local = MemorySparseTable(embedding_dim, rule=rule,
                                       initializer=initializer, seed=seed)
        # authoritative store: delta MERGE is row += delta, expressed as
        # the SGD rule at lr=1 applied to −delta (no second rule state)
        self._authority = ShardedSparseTable(
            embedding_dim, rule=SparseSGDRule(1.0),
            initializer=initializer, seed=seed, staleness=1,
            world=world, rank=rank, timeout_ms=timeout_ms)
        self._base = {}       # id -> row value at last sync
        self._refresh_cursor = 0
        self._dirty = set()
        self._push_count = 0

    def __len__(self):
        return len(self.local)

    def pull(self, ids):
        """Local rows; unseen ids fetch their authoritative base first
        (collective — every rank participates, possibly with an empty
        request)."""
        ids = np.asarray(ids).reshape(-1).astype(np.int64)
        uniq = np.unique(ids)
        new = np.array([i for i in uniq if int(i) not in self._base],
                       np.int64)
        if self.world > 1 or len(new):
            rows = self._authority.pull(new)
            if len(new):
                self.local.set_rows(new, rows)
                for i, r in zip(new, rows):
                    self._base[int(i)] = r.copy()
        return self.local.pull(ids)

    def push(self, ids, grads):
        """Apply immediately to the local copy; every sync_every-th call
        runs the collective geo round."""
        ids_flat = np.asarray(ids).reshape(-1).astype(np.int64)
        # push-only ids (never pulled): their base is the deterministic
        # initializer value — record it BEFORE the rule mutates the row,
        # no network round needed (see __init__'s initializer contract)
        new = np.array([i for i in np.unique(ids_flat)
                        if int(i) not in self._base], np.int64)
        if len(new):
            for i, r in zip(new, self._init_fn(len(new), new)):
                self._base[int(i)] = np.asarray(r, np.float32).copy()
        self.local.push(ids, grads)
        self._dirty.update(int(i) for i in ids_flat)
        self._push_count += 1
        if self._push_count % self.sync_every == 0:
            self.sync()

    def sync(self):
        """One geo round (collective): push local deltas for DIRTY ids,
        merge on owners, then refresh base/local for the dirty ids PLUS
        a rotating window of known ids — the recv half picks up other
        trainers' merged updates (reference GeoCommunicator send+recv
        per round) without pulling the whole touched vocabulary every
        round (refresh cost is bounded by dirty + refresh_chunk)."""
        dirty = np.array(sorted(self._dirty), np.int64)
        self._dirty.clear()
        if len(dirty):
            local_rows = self.local.pull(dirty)
            base_rows = np.stack([self._base[int(i)] for i in dirty])
            delta = local_rows - base_rows
        else:
            delta = np.zeros((0, self.dim), np.float32)
        # merge: authority_row += delta (SGD lr=1 on −delta), summed
        # over all trainers pushing the same id this round. The
        # authority runs at staleness=1, so push() flushes — no second
        # exchange round needed.
        self._authority.push(dirty, -delta)
        known_all = np.array(sorted(self._base), np.int64)
        lo = self._refresh_cursor
        window = known_all[lo:lo + self.refresh_chunk]
        self._refresh_cursor = (0 if lo + self.refresh_chunk
                                >= len(known_all)
                                else lo + self.refresh_chunk)
        refresh = np.unique(np.concatenate([dirty, window])) \
            if len(dirty) or len(window) else dirty
        merged = self._authority.pull(refresh)
        if len(refresh):
            self.local.set_rows(refresh, merged)
            for i, r in zip(refresh, merged):
                self._base[int(i)] = r.copy()

    def flush(self):
        self.sync()

    def state_dict(self):
        return self._authority.state_dict()

    def set_state_dict(self, sd):
        self._authority.set_state_dict(sd)
        # restored authority invalidates everything trainer-side: a
        # stale local/base pair would hide the load AND corrupt the
        # next merge with deltas against pre-restore values
        self.local = MemorySparseTable(self.dim, rule=self.local.rule,
                                       initializer=self._init_fn)
        self._base.clear()
        self._dirty.clear()
        self._refresh_cursor = 0


def ShardedEmbedding(num_embeddings, embedding_dim, axis="mp", **kwargs):
    """Factory: a dense nn.Embedding whose table is row-sharded over a
    mesh axis — the SPMD path when the vocabulary fits device memory
    (SparseCore-style; XLA lowers the gather to collectives over ICI).
    Usable inside DistributedTrainStep. Returns an Embedding instance
    (kept a function, not a subclass: the sharding is placement state on
    the weight, not behavior)."""
    from ..nn.layer.common import Embedding
    from jax.sharding import PartitionSpec as P

    layer = Embedding(num_embeddings, embedding_dim, **kwargs)
    layer.weight._pspec = P(axis, None)
    if mesh_mod.has_mesh():
        try:
            layer.weight._value = jax.device_put(
                layer.weight._value,
                mesh_mod.named_sharding(axis, None))
        except Exception as e:
            import warnings

            warnings.warn(
                f"ShardedEmbedding: placing the table on axis "
                f"{axis!r} failed ({e}); the weight stays REPLICATED "
                "until a parallel step re-shards it", RuntimeWarning)
    return layer
