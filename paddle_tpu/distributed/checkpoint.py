"""Sharded, asynchronous, atomic checkpointing.

TPU-native re-design of the reference checkpoint stack (reference:
python/paddle/framework/io.py:574 `paddle.save`, :791 `paddle.load`;
sharded gathering in fleet/meta_parallel/sharding/group_sharded_stage3.py:60
state_dict; auto-checkpoint fleet/utils/fs.py + incubate checkpoint).

Key differences from the reference design:

- **No gather on save.** The reference's stage-3 `state_dict()` all-gathers
  full params onto rank 0 before writing. Here every process writes only
  its *addressable* array shards (`Array.addressable_shards`), so a ZeRO-3
  / TP-sharded model checkpoints with zero cross-device traffic.
- **Async by construction.** A save is two phases. The synchronous
  SNAPSHOT phase runs on the step path: per-shard `copy_to_host_async`,
  host-buffer materialization, and ALL cross-rank coordination
  (barriers) — so the step can keep donating its buffers the moment it
  returns. The COMMIT phase (durable write → fsync → atomic rename) runs
  on a background thread with `async_save=True` and issues ZERO
  collectives: cross-rank completion is coordinated through per-rank
  ``DONE.<rank>`` marker files in the tmp dir (the `fleet/elastic`
  heartbeat file-protocol style), never through the coordination KV or
  XLA collectives — a writer-thread collective would race whatever the
  main thread dispatches meanwhile (mismatched programs → hang), which
  is exactly why the old design force-downgraded multi-process saves to
  synchronous. A second save issued while a commit is in flight
  back-pressures (joins the in-flight commit, journaled
  ``ckpt_backpressure``, counted into ``pt_ckpt_step_stall_seconds``).
- **Atomic commit.** Everything is written into `<dir>.tmp`; each rank
  drops its ``DONE.<rank>`` marker only after its shards + meta fragment
  are durable, and the rename into place happens exactly once (leader
  elected by ``COMMIT_LEADER`` O_EXCL) only after EVERY rank's marker is
  present — a killed job, or one killed rank, never leaves a
  half-checkpoint that `load_latest` would pick up. `is_complete`
  re-verifies the marker set against the ``commit.world`` recorded in
  meta.json, so even a hand-mutilated directory missing one rank's
  marker stays invisible (`pt_ckpt_incomplete_discarded_total`).

Layout::

    ckpt-000042/
      meta.json            # commit record: leaf table + commit.world
      DONE.<r>             # per-rank commit markers (all present by
                           # construction once meta.json is visible)
      shards/<leaf>#<k>.npy

Multi-controller jobs: each process writes its own shard files plus a
``meta.rank<r>.json`` fragment into the SHARED checkpoint filesystem;
the elected leader merges fragments and renames. Chaos scopes
``ckpt.snapshot`` / ``ckpt.commit`` / ``ckpt.commit.<rank>`` /
``ckpt.kill_window`` target the phases deterministically
(docs/RESILIENCE.md).
"""
import hashlib
import json
import os
import shutil
import threading
import time as _time

import numpy as np

import jax
import jax.numpy as jnp

from ..observability import metrics as _obs
from ..observability import steptrace as _steptrace
from ..observability.tracing import trace_span as _trace_span
from ..tensor_core import Tensor
from . import chaos
from .resilience import RetryPolicy, record

# telemetry (docs/OBSERVABILITY.md): durations, bytes moved, and the
# torn-checkpoint fallbacks that tell an operator a filesystem is
# eating commits
_SAVE_SECONDS = _obs.histogram("pt_ckpt_save_seconds",
                               "save_state_dict wall time")
_LOAD_SECONDS = _obs.histogram("pt_ckpt_load_seconds",
                               "load_state_dict wall time")
_BYTES_TOTAL = _obs.counter("pt_ckpt_bytes_total",
                            "checkpoint bytes, by direction",
                            labelnames=("direction",))
_OPS_TOTAL = _obs.counter("pt_ckpt_ops_total",
                          "completed checkpoint operations",
                          labelnames=("op",))
_TORN_FALLBACKS = _obs.counter(
    "pt_ckpt_torn_fallbacks_total",
    "torn checkpoints skipped by load_latest's older-checkpoint "
    "fallback")
_STALL_SECONDS = _obs.histogram(
    "pt_ckpt_step_stall_seconds",
    "time the training step path actually blocked on a save "
    "(back-pressure + snapshot phase; the commit runs off the step "
    "path under async_save)")
_COMMIT_SECONDS = _obs.histogram(
    "pt_ckpt_commit_seconds",
    "background COMMIT phase wall time (durable shard write -> rename "
    "visible)")
_INFLIGHT = _obs.gauge(
    "pt_ckpt_inflight", "checkpoint commits currently in flight")
_INCOMPLETE_DISCARDED = _obs.counter(
    "pt_ckpt_incomplete_discarded_total",
    "checkpoint dirs rejected because a rank's DONE commit marker is "
    "missing (counted once per directory per process)")

__all__ = ["save_state_dict", "load_state_dict", "Checkpointer",
           "verify_integrity", "TornCheckpointError"]


class TornCheckpointError(ValueError):
    """A checkpoint failed its meta.json integrity check (truncated or
    missing shards behind a committed meta). Distinct from the plain
    ValueError a model/optimizer structure mismatch raises, so
    load_latest's older-checkpoint fallback can never swallow the
    latter and silently restart a run from step 0."""


_META = "meta.json"

# two-phase commit protocol files (inside <path>.tmp): per-rank DONE
# markers + the leader-election lock for the final rename
_DONE_PREFIX = "DONE."
_LEADER = "COMMIT_LEADER"
# commit-phase marker-wait budget: bounded so a rank SIGKILLed before
# its marker can never wedge a surviving writer thread forever (the
# elastic layer restarts the pod long before this fires in practice)
_COMMIT_TIMEOUT_S = float(os.environ.get("PT_CKPT_COMMIT_TIMEOUT_S",
                                         "600"))
_POLL_S = 0.01

# Durability: fsync shard files, meta.json and the directories before the
# .tmp rename — without it a host crash right AFTER the rename can still
# lose the commit record (data in the page cache, rename journaled
# first). PT_CKPT_FSYNC=0 opts out (e.g. throwaway tmpfs test runs).
_FSYNC = os.environ.get("PT_CKPT_FSYNC", "1") != "0"


def _fsync_dir(path):
    if not _FSYNC:
        return
    try:
        fd = os.open(path, os.O_RDONLY)
    except OSError:
        return
    try:
        os.fsync(fd)
    finally:
        os.close(fd)


# ---------------------------------------------------------------- flatten

def _flatten(obj, path=(), list_paths=None):
    """Nested dict/list → [(path_tuple, leaf)]. Leaves: Tensor/jax/np
    arrays or JSON-able scalars. `list_paths` (a set, when given) records
    paths of list/tuple nodes so load can restore them as lists."""
    if isinstance(obj, dict):
        if not obj:
            return [(path, _EMPTY_DICT)]
        out = []
        for k, v in obj.items():
            out += _flatten(v, path + (str(k),), list_paths)
        return out
    if isinstance(obj, (list, tuple)) and not _is_leaf(obj):
        if list_paths is not None:
            list_paths.add("/".join(path))
        if not obj:
            return [(path, _EMPTY_LIST)]
        out = []
        for i, v in enumerate(obj):
            out += _flatten(v, path + (str(i),), list_paths)
        return out
    return [(path, obj)]


class _Sentinel:
    def __init__(self, tag):
        self.tag = tag


_EMPTY_DICT = _Sentinel("__empty_dict__")
_EMPTY_LIST = _Sentinel("__empty_list__")


def _is_leaf(obj):
    return isinstance(obj, (Tensor, jax.Array, np.ndarray, str, bytes,
                            int, float, bool, type(None)))


def _leaf_name(path):
    tail = "_".join(path[-2:]) if path else "leaf"
    safe = "".join(c if c.isalnum() or c in "._-" else "-" for c in tail)
    return f"{safe}.{hashlib.sha1('/'.join(path).encode()).hexdigest()[:10]}"


def _nest(flat, list_paths=()):
    """[(path, value)] → nested dicts; nodes recorded in `list_paths`
    (saved-side list/tuple containers, e.g. an LR scheduler's milestones)
    come back as lists ordered by integer key."""
    root = {}
    for path, v in flat:
        d = root
        for k in path[:-1]:
            d = d.setdefault(k, {})
        d[path[-1]] = v

    def _relist(node, path):
        if not isinstance(node, dict):
            return node
        out = {k: _relist(v, path + (k,)) for k, v in node.items()}
        if "/".join(path) in list_paths:
            return [out[k] for k in sorted(out, key=int)]
        return out

    return _relist(root, ())


_SAFE_NPY = {"float64", "float32", "float16", "int64", "int32", "int16",
             "int8", "uint8", "uint16", "uint32", "uint64", "bool"}
_VIEW_FOR_SIZE = {1: np.uint8, 2: np.uint16, 4: np.uint32, 8: np.uint64}


def _to_storage(nparr):
    """(storage_array, logical_dtype_str). bf16/fp8 etc. are stored as
    same-itemsize uints — npy would silently degrade them to void."""
    dt = str(nparr.dtype)
    if dt in _SAFE_NPY:
        return nparr, dt
    view = _VIEW_FOR_SIZE[nparr.dtype.itemsize]
    return nparr.view(view), dt


def _from_storage(nparr, logical_dtype):
    if str(nparr.dtype) == logical_dtype:
        return nparr
    return nparr.view(np.dtype(logical_dtype))  # ml_dtypes registers bf16 etc.


# ------------------------------------------------------------------- save

def _proc_index():
    try:
        return jax.process_index(), jax.process_count()
    except Exception:
        return 0, 1


# back-pressure: commits in flight in this process. A new save joins
# them before snapshotting (two concurrent commits to sibling dirs are
# safe, but unbounded pile-up under a slow filesystem would eat host
# RAM one full host-snapshot per lap) — the join time is step-path
# stall and is counted into pt_ckpt_step_stall_seconds.
_inflight_lock = threading.Lock()
_inflight = []


def _join_inflight():
    with _inflight_lock:
        handles = [h for h in _inflight if h.is_alive()]
    for h in handles:
        h.join()
    return bool(handles)


def save_state_dict(state, path, async_save=False, _stall_start=None):
    """Write `state` (nested dict of Tensors / arrays / scalars) to
    directory `path`. Every process saves only its addressable shards.

    The SNAPSHOT phase (everything up to the returned handle: D2H
    copies, host materialization, cross-rank barriers) is synchronous —
    after it, the caller may donate/overwrite every saved buffer. With
    async_save=True the COMMIT phase (durable write + marker protocol +
    rename) runs on a background thread and issues no collectives; this
    is safe at any process count. Returns a handle with .result()
    (joins the committer; re-raises errors); with async_save=False the
    checkpoint is complete and visible on return."""
    t_stall0 = _time.perf_counter() if _stall_start is None \
        else _stall_start
    if _join_inflight():
        record("ckpt_backpressure", path=path)
    rank, nproc = _proc_index()
    tmp = path + ".tmp"
    if rank == 0:
        if os.path.isdir(tmp):
            shutil.rmtree(tmp)
        os.makedirs(os.path.join(tmp, "shards"), exist_ok=True)
    if nproc > 1:
        from . import xproc

        xproc.barrier()  # tmp dir exists before anyone writes
        os.makedirs(os.path.join(tmp, "shards"), exist_ok=True)

    leaves, scalars, pending = [], {}, []
    list_paths, bytes_paths = set(), []
    empties = {}
    for p, leaf in _flatten(state, list_paths=list_paths):
        if any("/" in comp for comp in p):
            raise ValueError(
                f"state dict key {p!r} contains '/', which is the path "
                "separator — rename the key")
        key = "/".join(p)
        if isinstance(leaf, Tensor):
            leaf = leaf._value
        if isinstance(leaf, _Sentinel):
            empties[key] = leaf.tag
            continue
        if isinstance(leaf, np.generic):  # numpy scalar → python scalar
            leaf = leaf.item()
        if isinstance(leaf, (jax.Array, np.ndarray)):
            arr = leaf if isinstance(leaf, jax.Array) else jnp.asarray(leaf)
            entry = {"path": key, "shape": list(arr.shape),
                     "dtype": str(arr.dtype), "shards": []}
            base = _leaf_name(p)
            for k, sh in enumerate(arr.addressable_shards):
                if sh.replica_id != 0:
                    continue
                idx = [[(s.start or 0),
                        (s.stop if s.stop is not None else dim)]
                       for s, dim in zip(sh.index, arr.shape)]
                fname = f"{base}#r{rank}s{k}.npy"
                entry["shards"].append({"index": idx, "file": fname})
                try:
                    sh.data.copy_to_host_async()
                except Exception:  # ptlint: disable=PTL804 (prefetch hint; the sync copy path follows)
                    pass
                pending.append((os.path.join(tmp, "shards", fname), sh.data))
            leaves.append(entry)
        else:
            if isinstance(leaf, bytes):
                bytes_paths.append(key)
                leaf = leaf.decode("latin1")
            scalars[key] = leaf

    # Snapshot to host NOW, on the step path: compiled steps donate
    # param/opt buffers, so a device array held past this call may be
    # deleted — or updated IN PLACE — under the committer thread.
    # copy_to_host_async above pipelined the D2H transfers; this loop
    # mostly just collects them. On CPU backends np.asarray of a device
    # array is ZERO-COPY (the ISSUE-11 aliasing lesson): the "snapshot"
    # would be a live view of a donated buffer, and the overlapped
    # commit would serialize bytes the next train step is mutating —
    # force an owned host copy whenever the array aliases foreign
    # memory (`base is not None`; a real D2H transfer owns its buffer
    # and costs nothing extra here). Only durable file I/O is deferred
    # to the commit phase.
    def _own(dev_arr):
        host = np.asarray(dev_arr)
        return host.copy() if host.base is not None else host

    pending = [(fpath, _own(dev_arr)) for fpath, dev_arr in pending]
    # scope contract (chaos.py table): fires AFTER host materialization,
    # BEFORE the commit hand-off — the captured-but-uncommitted window
    chaos.fire("ckpt.snapshot")
    if nproc > 1:
        from . import xproc

        # every rank snapshotted — the LAST collective of this save;
        # the commit phase coordinates through marker files only
        xproc.barrier()

    committer = _Committer(
        tmp=tmp, path=path, rank=rank, nproc=nproc, pending=pending,
        leaves=leaves, scalars=scalars, lists=sorted(list_paths),
        bytes_paths=bytes_paths, empties=empties, t_start=t_stall0)
    if async_save:
        h = _AsyncHandle(committer.run)
        with _inflight_lock:
            _inflight.append(h)
        h.start()
        _STALL_SECONDS.observe(_time.perf_counter() - t_stall0)
        return h
    committer.run()
    # synchronous saves stall the step path for the whole commit — that
    # asymmetry IS the overlapped-checkpointing win the bench
    # ckpt_overlap_ab stamp measures. Observed on SUCCESS only: under
    # the Checkpointer retry policy each attempt re-enters with the
    # original _stall_start, so a per-attempt (finally) observation
    # would double-count the same logical save
    _STALL_SECONDS.observe(_time.perf_counter() - t_stall0)
    return _DoneHandle()


class _Committer:  # ptlint: thread-shared
    """The background COMMIT phase of one save: durable shard writes,
    the per-rank DONE marker protocol, and the leader-elected atomic
    rename. Runs on the caller thread (sync) or an _AsyncHandle thread
    (async). INVARIANT: no collectives and no coordination-KV traffic
    here, ever — a commit-thread collective would interleave with
    whatever program the main thread dispatches concurrently and hang
    the pod (the documented race that used to force multi-process
    saves synchronous). Cross-rank coordination is marker files on the
    shared checkpoint filesystem only."""

    def __init__(self, tmp, path, rank, nproc, pending, leaves, scalars,
                 lists, bytes_paths, empties, t_start):
        self.tmp = tmp
        self.path = path
        self.rank = rank
        self.nproc = nproc
        self.pending = pending
        self.leaves = leaves
        self.scalars = scalars
        self.lists = lists
        self.bytes_paths = bytes_paths
        self.empties = empties
        self.t_start = t_start

    def run(self):
        t0 = _time.perf_counter()
        _INFLIGHT.inc()
        try:
            with _trace_span("ckpt.save", path=self.path):
                self._commit_phase()
            _OPS_TOTAL.labels(op="save").inc()
            # duration from the CALLER's save start: includes snapshot
            # + any back-pressure, so async and sync report comparably
            _SAVE_SECONDS.observe(_time.perf_counter() - self.t_start)
        finally:
            _INFLIGHT.dec()
            _COMMIT_SECONDS.observe(_time.perf_counter() - t0)

    def _commit_phase(self):
        # deterministic chaos targets for the new phase (counted like
        # every scope: nth call of this scope on this rank)
        chaos.fire("ckpt.commit")
        chaos.fire(f"ckpt.commit.{self.rank}")
        n_bytes = 0
        for fpath, host_arr in self.pending:
            storage, _ = _to_storage(host_arr)
            n_bytes += storage.nbytes
            with open(fpath, "wb") as f:
                np.save(f, storage)
                if _FSYNC:
                    f.flush()
                    os.fsync(f.fileno())
        if self.nproc > 1:
            frag = {"leaves": self.leaves, "scalars": self.scalars,
                    "lists": self.lists, "bytes": self.bytes_paths,
                    "empties": self.empties}
            with open(os.path.join(self.tmp,
                                   f"meta.rank{self.rank}.json"),
                      "w") as f:
                json.dump(frag, f)
                if _FSYNC:
                    f.flush()
                    os.fsync(f.fileno())
        # THE torn-commit window: this rank's shards are on disk, its
        # commit marker is not — a kill here leaves the marker set
        # incomplete, so no rank can ever rename the tmp visible
        chaos.fire("ckpt.kill_window")
        marker = os.path.join(self.tmp, f"{_DONE_PREFIX}{self.rank}")
        with open(marker, "w") as f:
            f.write(str(_time.time()))
            if _FSYNC:
                f.flush()
                os.fsync(f.fileno())
        _fsync_dir(os.path.join(self.tmp, "shards"))
        _fsync_dir(self.tmp)
        self._await_markers()
        if self._claim_leader():
            self._finalize()
        self._await_visible()
        _BYTES_TOTAL.labels(direction="saved").inc(n_bytes)

    def _deadline(self):
        return _time.monotonic() + _COMMIT_TIMEOUT_S

    def _visible(self):
        """The rename happened: tmp is gone (a peer — or this rank —
        published the checkpoint)."""
        return not os.path.isdir(self.tmp)

    def _await_markers(self):
        deadline = self._deadline()
        while True:
            if self._visible():
                return
            if all(os.path.exists(
                    os.path.join(self.tmp, f"{_DONE_PREFIX}{r}"))
                    for r in range(self.nproc)):
                return
            if _time.monotonic() > deadline:
                record("ckpt_commit_timeout", path=self.path,
                       phase="markers", rank=self.rank)
                raise TimeoutError(
                    f"ckpt commit {self.path}: not every rank's "
                    f"{_DONE_PREFIX}<r> marker appeared within "
                    f"{_COMMIT_TIMEOUT_S:.0f}s — a peer likely died "
                    "mid-commit; this checkpoint stays invisible and "
                    "load_latest falls back to the previous one")
            _time.sleep(_POLL_S)

    def _claim_leader(self):
        """Exactly-once rename election: O_CREAT|O_EXCL on the shared
        lock file. Claimed only after every marker is present, so the
        leader is guaranteed to see all fragments."""
        try:
            fd = os.open(os.path.join(self.tmp, _LEADER),
                         os.O_CREAT | os.O_EXCL | os.O_WRONLY)
        except FileExistsError:
            return False
        except FileNotFoundError:
            return False        # a peer already renamed tmp away
        os.close(fd)
        return True

    def _finalize(self):
        if self.nproc > 1:
            seen_scalars, by_path, empt = {}, {}, {}
            lists, byts = set(), set()
            for r in range(self.nproc):
                with open(os.path.join(
                        self.tmp, f"meta.rank{r}.json")) as f:
                    fr = json.load(f)
                seen_scalars.update(fr["scalars"])
                lists.update(fr["lists"])
                byts.update(fr["bytes"])
                empt.update(fr.get("empties", {}))
                for e in fr["leaves"]:
                    tgt = by_path.setdefault(e["path"], e)
                    if tgt is not e:
                        tgt["shards"] += e["shards"]
            _commit(self.tmp, self.path, list(by_path.values()),
                    seen_scalars, sorted(lists), sorted(byts), empt,
                    world=self.nproc)
        else:
            _commit(self.tmp, self.path, self.leaves, self.scalars,
                    self.lists, self.bytes_paths, self.empties,
                    world=1)

    def _await_visible(self):
        deadline = self._deadline()
        while not self._visible():
            if _time.monotonic() > deadline:
                record("ckpt_commit_timeout", path=self.path,
                       phase="rename", rank=self.rank)
                raise TimeoutError(
                    f"ckpt commit {self.path}: the elected leader never "
                    f"published the rename within "
                    f"{_COMMIT_TIMEOUT_S:.0f}s")
            _time.sleep(_POLL_S)


def _commit(tmp, path, leaves, scalars, list_paths=(), bytes_paths=(),
            empties=None, world=1):
    # integrity record: leaf count + per-shard byte size, so load can
    # reject a torn checkpoint (shard truncated/missing despite a
    # committed meta.json) instead of half-loading it. `commit.world`
    # records how many DONE.<r> markers is_complete must re-verify.
    shard_sizes = {}
    for e in leaves:
        for srec in e["shards"]:
            shard_sizes[srec["file"]] = os.path.getsize(
                os.path.join(tmp, "shards", srec["file"]))
    with open(os.path.join(tmp, _META), "w") as f:
        json.dump({"leaves": leaves, "scalars": scalars,
                   "lists": list(list_paths),
                   "bytes": list(bytes_paths),
                   "empties": empties or {},
                   "commit": {"world": int(world)},
                   "integrity": {"leaf_count": len(leaves),
                                 "shards": shard_sizes}}, f)
        if _FSYNC:
            f.flush()
            os.fsync(f.fileno())
    # directory entries (shard files + meta.json) durable BEFORE the
    # rename publishes them
    _fsync_dir(os.path.join(tmp, "shards"))
    _fsync_dir(tmp)
    if os.path.isdir(path):
        shutil.rmtree(path)
    os.replace(tmp, path)
    # the rename itself durable: fsync the parent directory
    _fsync_dir(os.path.dirname(os.path.abspath(path)))


def verify_integrity(path):
    """Validate a checkpoint directory against its meta.json integrity
    record (leaf count + per-shard byte sizes). Raises
    TornCheckpointError on a torn checkpoint; checkpoints written before
    the integrity record pass (nothing to check). Returns the parsed
    meta."""
    try:
        with open(os.path.join(path, _META)) as f:
            meta = json.load(f)
    except (json.JSONDecodeError, UnicodeDecodeError) as e:
        # a truncated/garbled meta.json (host crash with PT_CKPT_FSYNC=0,
        # or a pre-fsync checkpoint) is a torn checkpoint, not a caller
        # bug — classify it so load_latest falls back to the next-older
        # complete checkpoint instead of crashing the resume
        raise TornCheckpointError(
            f"torn checkpoint {path}: unreadable {_META}: {e}") from e
    integ = meta.get("integrity")
    if integ is None:
        return meta
    if len(meta["leaves"]) != integ["leaf_count"]:
        raise TornCheckpointError(
            f"torn checkpoint {path}: meta lists {len(meta['leaves'])} "
            f"leaves, integrity record expects {integ['leaf_count']}")
    sizes = integ["shards"]
    for e in meta["leaves"]:
        for srec in e["shards"]:
            fname = srec["file"]
            if fname not in sizes:
                raise TornCheckpointError(
                    f"torn checkpoint {path}: shard {fname} missing "
                    "from integrity record")
            fpath = os.path.join(path, "shards", fname)
            try:
                actual = os.path.getsize(fpath)
            except OSError:
                raise TornCheckpointError(
                    f"torn checkpoint {path}: shard {fname} missing")
            if actual != sizes[fname]:
                raise TornCheckpointError(
                    f"torn checkpoint {path}: shard {fname} is {actual} "
                    f"bytes, committed as {sizes[fname]}")
    return meta


class _AsyncHandle(threading.Thread):
    def __init__(self, fn):
        super().__init__(daemon=True)
        self._fn = fn
        self._err = None

    def run(self):
        try:
            self._fn()
        except BaseException as e:  # surfaced in result()
            self._err = e
        finally:
            with _inflight_lock:
                try:
                    _inflight.remove(self)
                except ValueError:
                    pass

    def result(self):
        self.join()
        if self._err is not None:
            raise self._err


class _DoneHandle:
    def result(self):
        return None


# ------------------------------------------------------------------- load

# incomplete dirs counted once per path per process (is_complete runs
# on every steps() scan — a raw per-call count would just measure scan
# frequency); completeness VERDICTS are cached the same way, because a
# published checkpoint dir is immutable and meta.json embeds the full
# per-shard index — re-parsing it on every _prune/load_latest scan
# would put keep× full-JSON parses on the step path
_incomplete_seen_lock = threading.Lock()
_incomplete_seen = set()
_complete_seen = set()


def is_complete(path):
    """A committed checkpoint: meta.json present AND every rank's
    DONE.<r> commit marker (per meta's commit.world) present. By
    construction the rename that publishes meta.json only happens after
    all markers exist, so a missing marker means tampering or a
    pre-marker-protocol bug — either way the directory is invisible
    (pt_ckpt_incomplete_discarded_total), never half-trusted.
    Checkpoints written before the commit record pass on meta.json
    alone; an unreadable meta.json is left for verify_integrity to
    classify as torn."""
    meta_p = os.path.join(path, _META)
    if not os.path.isfile(meta_p):
        return False
    with _incomplete_seen_lock:
        if path in _complete_seen:
            return True
    try:
        with open(meta_p) as f:
            world = int((json.load(f).get("commit") or {})
                        .get("world", 0))
    except (json.JSONDecodeError, UnicodeDecodeError, OSError,
            TypeError, ValueError):
        return True    # torn meta: load_latest's fallback handles it
    missing = [r for r in range(world)
               if not os.path.isfile(
                   os.path.join(path, f"{_DONE_PREFIX}{r}"))]
    if not missing:
        with _incomplete_seen_lock:
            _complete_seen.add(path)
        return True
    with _incomplete_seen_lock:
        if path not in _incomplete_seen:
            _incomplete_seen.add(path)
            _INCOMPLETE_DISCARDED.inc()
            record("ckpt_incomplete", path=path, missing_ranks=missing)
    return False


def load_state_dict(path, shardings=None, return_numpy=False):
    """Load a checkpoint directory into a nested dict. Array leaves come
    back as Tensors (or numpy with return_numpy=True). `shardings` maps
    leaf path ("a/b/c") → jax.sharding.Sharding to place a leaf sharded
    (only the locally-needed regions are copied to each device; shard
    files are memory-mapped, so an N-way-sharded leaf never materializes
    fully per-host).

    The meta.json integrity record (leaf count + per-shard byte sizes)
    is verified first: a torn checkpoint is rejected with ValueError,
    never half-loaded."""
    t_start = _time.perf_counter()
    meta = verify_integrity(path)
    flat = []
    for e in meta["leaves"]:
        shape = tuple(e["shape"])
        dtype = e["dtype"]
        mmaps = []
        for srec in e["shards"]:
            m = np.load(os.path.join(path, "shards", srec["file"]),
                        mmap_mode="r")
            mmaps.append((tuple((a, b) for a, b in srec["index"]), m))

        def _region(idx, _mm=mmaps, _shape=shape, _dt=dtype):
            """Assemble the region `idx` (tuple of slices) from shards."""
            starts = [s.start or 0 for s in idx]
            stops = [s.stop if s.stop is not None else d
                     for s, d in zip(idx, _shape)]
            out = np.empty([b - a for a, b in zip(starts, stops)],
                           dtype=np.dtype(_mm[0][1].dtype))
            for bounds, m in _mm:
                inter = [(max(a, s), min(b, e))
                         for (a, b), s, e in zip(bounds, starts, stops)]
                if any(lo >= hi for lo, hi in inter):
                    continue
                src = tuple(slice(lo - a, hi - a)
                            for (a, _), (lo, hi) in zip(bounds, inter))
                dst = tuple(slice(lo - s, hi - s)
                            for s, (lo, hi) in zip(starts, inter))
                out[dst] = m[src]
            return _from_storage(out, _dt)

        key = e["path"]
        sh = (shardings or {}).get(key)
        if sh is not None:
            arr = jax.make_array_from_callback(shape, sh, _region)
        else:
            full = _region(tuple(slice(0, d) for d in shape))
            arr = np.asarray(full) if return_numpy else jnp.asarray(full)
        flat.append((tuple(key.split("/")),
                     arr if return_numpy else Tensor(arr)))
    byts = set(meta.get("bytes", ()))
    for key, v in meta["scalars"].items():
        if key in byts:
            v = v.encode("latin1")
        flat.append((tuple(key.split("/")), v))
    for key, tag in meta.get("empties", {}).items():
        flat.append((tuple(key.split("/")),
                     {} if tag == "__empty_dict__" else []))
    out = _nest(flat, set(meta.get("lists", ())))
    integ = meta.get("integrity") or {}
    _BYTES_TOTAL.labels(direction="loaded").inc(
        sum(integ.get("shards", {}).values()))
    _OPS_TOTAL.labels(op="load").inc()
    _LOAD_SECONDS.observe(_time.perf_counter() - t_start)
    return out


def _xla_owned(arr):
    """Re-ingest a restored leaf through a trivial on-device program so
    the result's buffer is ALLOCATED AND OWNED BY XLA, preserving
    sharding and commitment (elementwise ops keep both; verified for
    this jax build).

    Root-caused this session: `jax.make_array_from_callback` ALIASES
    the callback's numpy buffers on CPU (np↔jnp zero-copy is the same
    family), so a checkpoint-restored sharded param/accumulator entered
    the DONATING train-step executable backed by numpy-owned memory —
    and when the persistent compile cache serves the executable with
    true in-place donation, XLA reuses/frees host memory numpy still
    owns: heap corruption ('corrupted double-linked list' at the second
    post-restore dispatch or at exit, ~2-in-3 runs on the hybrid3d
    restore path). This is the PTL201 'zero-copy route into a donated
    pytree' signature (docs/RESILIENCE.md 'Buffer aliasing'), at the
    checkpoint-restore ingest boundary. One device-local memcpy per
    restored leaf buys ownership."""
    if not isinstance(arr, jax.Array):
        return arr
    if arr.dtype == jnp.bool_:
        return jnp.logical_or(arr, False)
    return arr + jnp.zeros((), arr.dtype)


# ----------------------------------------------------------- Checkpointer

class Checkpointer:
    """Train-loop checkpoint manager (reference auto-checkpoint /
    fleet.utils fs checkpoint + hapi callbacks ModelCheckpoint).

    save(step) captures model params, optimizer accumulators + LR-scheduler
    state, and a compiled train step's device-side opt states; keeps the
    newest `keep` checkpoints; `async_save` overlaps file writes with
    training. load_latest() restores everything and returns the step (or
    None if no complete checkpoint exists)."""

    def __init__(self, root, model=None, optimizer=None, train_step=None,
                 keep=3, async_save=False, retry=None):
        self.root = root
        self.model = model
        self.train_step = train_step
        self.optimizer = optimizer or (
            train_step.optimizer if train_step is not None else None)
        self.keep = keep
        self.async_save = async_save
        self._last = None
        # transient-FS hardening (flaky NFS/GCS-fuse mounts): loads are
        # always retried; saves only single-process + synchronous, where
        # re-running is idempotent (a multi-controller save re-run on one
        # rank alone would re-enter the snapshot barrier without its
        # peers and desync the pod — that path relies on the marker
        # protocol's invisible-until-complete guarantee plus the elastic
        # restart layer instead)
        # give_up_on FileNotFoundError: a missing shard behind a
        # committed meta is a TORN checkpoint (load_latest's fallback
        # signal), never a transient — don't burn backoff sleeps on it
        self.retry = retry or RetryPolicy(
            max_attempts=3, base_s=0.2, max_backoff_s=2.0,
            retry_on=(OSError,), give_up_on=(FileNotFoundError,),
            name="ckpt.io")

    def _dir(self, step):
        return os.path.join(self.root, f"ckpt-{step:08d}")

    def _name_maps(self):
        """param.name ↔ structural-key maps. Parameter.name comes from a
        process-global counter, so it differs across re-instantiation;
        checkpoints must be keyed by the structural state_dict key."""
        by_pname, by_struct = {}, {}
        if self.model is not None:
            for sname, p in self.model.state_dict().items():
                by_pname[p.name] = sname
                by_struct[sname] = p.name
        return by_pname, by_struct

    @staticmethod
    def _remap_opt_keys(sd, mapping):
        """optimizer.state_dict keys look like f'{param.name}_{acc}';
        rewrite the param.name prefix via mapping (longest-prefix match).
        Non-param keys (@step, LR_Scheduler) pass through."""
        pnames = sorted(mapping, key=len, reverse=True)
        out = {}
        for k, v in sd.items():
            nk = k
            for pn in pnames:
                if k.startswith(pn + "_"):
                    nk = mapping[pn] + k[len(pn):]
                    break
            out[nk] = v
        return out

    def save(self, step):
        # back-pressure: a still-running commit of the previous save is
        # joined HERE (error-propagating), and the wait counts into the
        # step-path stall this save reports
        t_stall0 = _time.perf_counter()
        if isinstance(self._last, _AsyncHandle) and self._last.is_alive():
            record("ckpt_backpressure", step=int(step))
        self.wait()
        state = {"step": int(step)}
        if self.model is not None:
            state["model"] = dict(self.model.state_dict())
        if self.optimizer is not None:
            by_pname, _ = self._name_maps()
            state["optimizer"] = self._remap_opt_keys(
                self.optimizer.state_dict(), by_pname)
        if self.train_step is not None:
            opt_sd = _train_step_opt_states(self.train_step)
            if opt_sd:
                state["train_step_opt"] = opt_sd
        _, nproc = _proc_index()
        t_wall0 = _steptrace.now()
        if nproc == 1 and not self.async_save:
            self._last = self.retry.run(
                save_state_dict, state, self._dir(step),
                name=f"ckpt.save:{step}", _stall_start=t_stall0)
        else:
            self._last = save_state_dict(state, self._dir(step),
                                         async_save=self.async_save,
                                         _stall_start=t_stall0)
        # the synchronous slice of this save (snapshot + commit
        # hand-off; async commits run off the step path) becomes the
        # next step's ckpt_snapshot phase segment — the wall-time the
        # training loop actually lost to checkpointing
        _steptrace.note_ckpt_snapshot(t_wall0, _steptrace.now())
        self._prune()
        return self._last

    def wait(self):
        if self._last is not None:
            self._last.result()
            self._last = None

    def _prune(self):
        if not self.keep:
            return
        rank, _ = _proc_index()
        if rank != 0:
            return
        steps = sorted(self.steps())
        for s in steps[: max(0, len(steps) - self.keep)]:
            shutil.rmtree(self._dir(s), ignore_errors=True)

    def steps(self):
        if not os.path.isdir(self.root):
            return []
        out = []
        for d in os.listdir(self.root):
            if d.startswith("ckpt-") and is_complete(
                    os.path.join(self.root, d)):
                try:
                    out.append(int(d.split("-")[1]))
                except ValueError:
                    pass
        return sorted(out)

    def load_latest(self):
        """Restore from the newest COMPLETE checkpoint. A checkpoint
        that fails its integrity check (torn shards despite a committed
        meta.json — pre-fsync checkpoints could do this after a host
        crash) is journaled and skipped, falling back to the next-older
        one instead of half-loading. ONLY torn-checkpoint shapes
        (TornCheckpointError, missing shard files) fall back — a
        transient I/O failure that survives the retry budget, or a
        model/optimizer structure mismatch, propagates, so neither a
        flaky filesystem nor a changed model can masquerade as "no
        checkpoints" and silently restart a long run from step 0."""
        from .resilience import RetryError

        for step in reversed(self.steps()):
            try:
                return self.load(step)
            except TornCheckpointError as e:
                _TORN_FALLBACKS.inc()
                record("ckpt_rejected", step=step, error=str(e))
                continue
            except RetryError as e:
                if isinstance(e.last, FileNotFoundError):
                    _TORN_FALLBACKS.inc()
                    record("ckpt_rejected", step=step, error=str(e))
                    continue
                raise
        return None

    def load(self, step):
        # Place param leaves straight onto their current shardings
        # (ZeRO/TP) — but ONLY for leaves whose live array is committed.
        # make_array_from_callback yields committed arrays, and a
        # committed leaf where the live one was uncommitted lowers the
        # compiled TrainStep differently; with the persistent compile
        # cache the two variants collide on one cache entry and the
        # mismatched donation/aliasing map silently reverts the first
        # post-restore update (flaky resume-divergence, see
        # test_train_kill_resume_matches_uninterrupted). Committed live
        # arrays (device_put with an explicit NamedSharding — the real
        # ZeRO/TP case) keep the shard-for-shard mmap load.
        shardings = {}
        if self.model is not None:
            for name, p in self.model.state_dict().items():
                if isinstance(p._value, jax.Array) and p._value.committed:
                    shardings[f"model/{name}"] = p._value.sharding
        ts = self.train_step
        if ts is not None and getattr(ts, "_opt_states", None):
            # accumulators of a live compiled step load shard-for-shard
            # too (they are 2x param bytes under Adam — never assemble
            # them fully per host)
            for n, st in zip(_train_names(ts), ts._opt_states):
                for k, v in st.items():
                    if isinstance(v, jax.Array) and v.committed:
                        shardings[f"train_step_opt/{n}/{k}"] = v.sharding
        with _trace_span("ckpt.load", step=step):
            state = self.retry.run(load_state_dict, self._dir(step),
                                   shardings=shardings,
                                   name=f"ckpt.load:{step}")
        if self.model is not None and "model" in state:
            sd = self.model.state_dict()
            missing = [n for n in sd if n not in state["model"]]
            if missing:
                raise ValueError(
                    f"checkpoint is missing model params {missing}; "
                    "model structure differs from the one checkpointed")
            for name, p in sd.items():
                # _xla_owned: the restored array may alias numpy-owned
                # region buffers (make_array_from_callback) — donated
                # in place by the compiled step, that memory corrupts
                # the host heap; re-ingest to an XLA-owned buffer
                p._value = _xla_owned(
                    state["model"][name]._value.astype(p._value.dtype))
        if self.optimizer is not None and "optimizer" in state:
            _, by_struct = self._name_maps()
            self.optimizer.set_state_dict(self._remap_opt_keys(
                state["optimizer"], by_struct))
        if self.train_step is not None and "train_step_opt" in state:
            _restore_train_step_opt(self.train_step,
                                    state["train_step_opt"])
        return int(state["step"])


def _train_names(ts):
    """Structural (state_dict-key) names of trainable params — stable
    across model re-instantiation, unlike global Parameter.name counters."""
    return [n for n, t in zip(ts._names, ts._trainable) if t]


def _train_step_opt_states(ts):
    """Device-side accumulator tree of a compiled TrainStep /
    DistributedTrainStep, keyed structural-param-name → accumulator."""
    if getattr(ts, "_opt_states", None) is None:
        return {}
    if all(not st for st in ts._opt_states):
        return {}  # stateless optimizer (SGD) — nothing to record
    return {n: dict(st)
            for n, st in zip(_train_names(ts), ts._opt_states)}


def _restore_train_step_opt(ts, opt_sd):
    names = _train_names(ts)
    missing = [n for n in names if n not in opt_sd]
    if missing:
        raise ValueError(
            f"checkpoint is missing optimizer state for params {missing}; "
            "model structure differs from the one checkpointed")
    old = ts._opt_states
    states = []
    for i, n in enumerate(names):
        st = opt_sd[n]
        d = {}
        for k, v in st.items():
            val = v._value if isinstance(v, Tensor) else jnp.asarray(v)
            if (old is not None and isinstance(old[i].get(k), jax.Array)
                    and old[i][k].committed):
                # step already ran on COMMITTED accumulators (mesh
                # placement): re-place onto the live sharding (the
                # _build-time device_put won't run again)
                val = jax.device_put(val, old[i][k].sharding)
            elif not isinstance(val, jax.Array) or val.committed:
                # live accumulators are UNCOMMITTED (the single-device
                # _build path) — restore them uncommitted too:
                # device_put here yields committed arrays, which flips
                # the step's jit signature and costs a second
                # executable after the restore.
                val = jnp.asarray(np.asarray(val))
            # donated next step — must be XLA-owned (see _xla_owned)
            d[k] = _xla_owned(val)
        states.append(d)
    ts._opt_states = states
