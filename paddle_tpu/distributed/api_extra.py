"""Remaining paddle.distributed surface: spawn, ParallelMode, TP split,
gloo facade, PS dataset facades and sparse-entry configs.

Reference: python/paddle/distributed/{spawn.py, parallel.py,
collective.py split:?, fleet/dataset/, entry_attr}.
"""
import os
import sys

import numpy as np

__all__ = [
    "ParallelMode", "spawn", "split", "destroy_process_group",
    "gloo_init_parallel_env", "gloo_barrier", "gloo_release",
    "InMemoryDataset", "QueueDataset", "BoxPSDataset",
    "ProbabilityEntry", "CountFilterEntry", "ShowClickEntry",
]


class ParallelMode:
    """Hybrid-parallel mode ids (reference:
    python/paddle/distributed/parallel.py ParallelMode)."""

    DATA_PARALLEL = 0
    TENSOR_PARALLEL = 1
    PIPELINE_PARALLEL = 2
    SHARDING_PARALLEL = 3


def spawn(func, args=(), nprocs=-1, join=True, daemon=False, **options):
    """Run `func(*args)` in nprocs worker processes under the PADDLE_*
    env contract (reference: distributed/spawn.py). Each worker calls
    init_parallel_env itself (as in the reference examples). Workers
    inherit the parent's environment, `JAX_PLATFORMS` included; on a
    TPU host call this BEFORE the parent touches jax — a chip belongs
    to one process at a time."""
    import multiprocessing as mp
    import socket

    if nprocs == -1:
        nprocs = int(os.environ.get("PADDLE_TRAINERS_NUM", "1"))
    if nprocs <= 1:
        func(*args)
        return None
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        master = f"127.0.0.1:{s.getsockname()[1]}"
    ctx = mp.get_context("spawn")
    procs = []
    for rank in range(nprocs):
        env = {
            "PADDLE_MASTER": master,
            "PADDLE_TRAINER_ID": str(rank),
            "PADDLE_TRAINERS_NUM": str(nprocs),
            "PADDLE_LOCAL_RANK": str(rank),
            "PADDLE_LOCAL_SIZE": str(nprocs),
        }
        p = ctx.Process(target=_spawn_entry, args=(func, args, env),
                        daemon=daemon)
        p.start()
        procs.append(p)
    if join:
        for p in procs:
            p.join()
        bad = [p.exitcode for p in procs if p.exitcode != 0]
        if bad:
            raise RuntimeError(f"spawn workers failed with codes {bad}")
        return None
    return procs


def _spawn_entry(func, args, env):
    os.environ.update(env)
    func(*args)


def split(x, size, operation, axis=0, num_partitions=1, gather_out=True,
          weight_attr=None, bias_attr=None, name=None):
    """Megatron-style single-op model parallelism (reference:
    python/paddle/distributed/collective.py split): build the matching
    mpu layer over the mp mesh axis and apply it. Prefer the
    fleet.meta_parallel layers for real models — they own their
    parameters across steps; this op-level facade constructs the layer
    per call (same as the reference's LayerHelper-created vars)."""
    from .fleet.meta_parallel import mp_layers as mpu

    if operation == "linear":
        in_f, out_f = size
        if axis == 1:
            layer = mpu.ColumnParallelLinear(
                in_f, out_f, weight_attr=weight_attr,
                has_bias=bias_attr is not False,
                gather_output=gather_out)
        else:
            layer = mpu.RowParallelLinear(
                in_f, out_f, weight_attr=weight_attr,
                has_bias=bias_attr is not False,
                input_is_parallel=not gather_out)
        return layer(x)
    if operation == "embedding":
        vocab, dim = size
        layer = mpu.VocabParallelEmbedding(vocab, dim,
                                           weight_attr=weight_attr)
        return layer(x)
    raise ValueError(f"unsupported split operation {operation!r}")


def destroy_process_group(group=None):
    """Tear down group state (reference: collective.py
    destroy_process_group)."""
    from . import collective

    if group is None:
        collective._groups.clear()
        return
    collective._groups.pop(getattr(group, "id", group), None)


def gloo_init_parallel_env(rank_id, rank_num, server_endpoint):
    """CPU-barrier rendezvous (reference: parallel.py gloo_init_parallel_env).
    The jax.distributed coordination service subsumes gloo: ensure it is
    up for this process set."""
    from . import env as env_mod

    os.environ.setdefault("PADDLE_TRAINER_ID", str(rank_id))
    os.environ.setdefault("PADDLE_TRAINERS_NUM", str(rank_num))
    os.environ.setdefault("PADDLE_MASTER", server_endpoint)
    env_mod.ensure_multihost_initialized()


def gloo_barrier():
    from . import xproc

    if xproc.is_multiprocess():
        xproc.barrier()


def gloo_release():
    """No resources to free: the coordination service dies with the
    process set."""


# ---------------------------------------------------------- PS datasets

class _SlotDataset:
    """Slot-based dataset facade for PS training (reference:
    python/paddle/distributed/fleet/dataset/dataset.py InMemoryDataset /
    QueueDataset over C++ data_feed.cc). Files hold one sample per line;
    `pipe_command` is replaced by a python `parse_fn` (no subprocess feed
    on the TPU host path)."""

    def __init__(self):
        self._filelist = []
        self._samples = []
        self._batch_size = 1
        self._use_var = []
        self._parse_fn = None
        self._thread_num = 1

    def init(self, batch_size=1, thread_num=1, use_var=None, pipe_command=None,
             parse_fn=None, **kwargs):
        self._batch_size = batch_size
        self._thread_num = thread_num
        self._use_var = use_var or []
        self._parse_fn = parse_fn

    update_settings = init

    def set_filelist(self, filelist):
        self._filelist = list(filelist)

    def _iter_lines(self):
        if self._parse_fn == "numeric":
            # native fast path: C strtof loop over newline-aligned chunks
            # (reference data_feed.cc MultiSlotDataFeed), GIL released.
            # Chunked so QueueDataset stays streaming on huge files.
            from .. import native

            n_slots = len(self._use_var) if self._use_var else None
            chunk_size = 4 << 20
            for path in self._filelist:
                with open(path, "rb") as f:
                    pending = b""
                    while True:
                        chunk = f.read(chunk_size)
                        if chunk:
                            data = pending + chunk
                            nl = data.rfind(b"\n")
                            if nl < 0:
                                pending = data
                                continue
                            pending, data = data[nl + 1:], data[: nl + 1]
                        else:
                            data, pending = pending, b""
                        if n_slots is None:
                            for line in data.split(b"\n"):
                                if line.strip():
                                    n_slots = len(line.split())
                                    break
                        if data.strip() and n_slots:
                            for row in native.parse_slots(data, n_slots):
                                yield row.tolist()
                        if not chunk:
                            break
            return
        for path in self._filelist:
            with open(path) as f:
                for line in f:
                    line = line.strip()
                    if not line:
                        continue
                    yield (self._parse_fn(line) if self._parse_fn
                           else line.split())

    def __iter__(self):
        buf = []
        for sample in self._iter_lines():
            buf.append(sample)
            if len(buf) == self._batch_size:
                yield buf
                buf = []
        if buf:
            yield buf


class InMemoryDataset(_SlotDataset):
    def __init__(self):
        super().__init__()
        self._loaded = False

    def load_into_memory(self):
        self._samples = list(self._iter_lines())
        self._loaded = True

    def get_memory_data_size(self, fleet=None):
        return len(self._samples)

    def local_shuffle(self):
        np.random.default_rng().shuffle(self._samples)

    _shuffle_calls = 0

    def global_shuffle(self, fleet=None, thread_num=12):
        """Cross-trainer shuffle (reference: data_set.cc distributed
        shuffle — samples are re-partitioned across all trainers by
        random owner, then shuffled locally). Buckets travel POINT TO
        POINT over the coordination-service KV store (each pair
        exchanges only its bucket — O(N) total, not an O(N·world)
        padded all-gather). The owner draw mixes in a per-call counter
        so each epoch re-draws the partition. Single-process: local."""
        import pickle

        from . import xproc

        self._shuffle_calls += 1
        if not xproc.is_multiprocess():
            self.local_shuffle()
            return
        import jax

        world = jax.process_count()
        me = jax.process_index()
        rng = np.random.default_rng([me, self._shuffle_calls])
        owners = rng.integers(0, world, len(self._samples))
        outgoing = [[] for _ in range(world)]
        for s, o in zip(self._samples, owners):
            outgoing[int(o)].append(s)
        mine = list(outgoing[me])
        tag = 7000 + (self._shuffle_calls % 1000)
        for peer in range(world):
            if peer != me:
                xproc.send_bytes(pickle.dumps(
                    outgoing[peer], protocol=pickle.HIGHEST_PROTOCOL),
                    dst=peer, tag=tag)
        for peer in range(world):
            if peer != me:
                mine.extend(pickle.loads(
                    xproc.recv_bytes(src=peer, tag=tag)))
        self._samples = mine
        self.local_shuffle()

    def release_memory(self):
        self._samples = []
        self._loaded = False

    def get_shuffle_data_size(self, fleet=None):
        return len(self._samples)

    def __iter__(self):
        src = self._samples if self._loaded else self._iter_lines()
        buf = []
        for sample in src:
            buf.append(sample)
            if len(buf) == self._batch_size:
                yield buf
                buf = []
        if buf:
            yield buf


class QueueDataset(_SlotDataset):
    """Streaming variant — never materializes the file set."""


class BoxPSDataset(InMemoryDataset):
    """BoxPS (ads) dataset facade; behaviorally InMemoryDataset here
    (reference dataset.py BoxPSDataset adds PS-server preload hooks)."""

    def begin_pass(self):
        pass

    def end_pass(self, need_save_delta=False):
        pass

    def preload_into_memory(self):
        self.load_into_memory()

    def wait_preload_done(self):
        pass


# ----------------------------------------------- sparse entry policies

class ProbabilityEntry:
    """Random-admission policy for sparse features (reference:
    python/paddle/distributed/entry_attr.py ProbabilityEntry)."""

    def __init__(self, probability):
        if not 0 < probability <= 1:
            raise ValueError("probability must be in (0, 1]")
        self._probability = float(probability)

    def _to_attr(self):
        return f"probability_entry:{self._probability}"


class CountFilterEntry:
    """Admit a feature only after `count_filter` occurrences (reference:
    entry_attr.py CountFilterEntry)."""

    def __init__(self, count_filter):
        if count_filter < 0:
            raise ValueError("count_filter must be >= 0")
        self._count_filter = int(count_filter)

    def _to_attr(self):
        return f"count_filter_entry:{self._count_filter}"


class ShowClickEntry:
    """Show/click-weighted entry (reference: entry_attr.py ShowClickEntry)."""

    def __init__(self, show_name, click_name):
        self._show = str(show_name)
        self._click = str(click_name)

    def _to_attr(self):
        return f"show_click_entry:{self._show}:{self._click}"
