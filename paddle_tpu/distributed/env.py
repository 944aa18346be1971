"""Rank/world-size environment contract.

(Reference env vars: PADDLE_TRAINER_ID / PADDLE_TRAINERS_NUM /
PADDLE_TRAINER_ENDPOINTS — python/paddle/distributed/parallel.py:94.)
On TPU pods jax.distributed supplies process_index/process_count once
initialized; before that, the launcher env contract applies.
"""
import os

__all__ = ["get_rank", "get_world_size", "ParallelEnv",
           "ensure_multihost_initialized"]


def ensure_multihost_initialized():
    """Multi-controller bring-up: if the launcher env contract names a
    coordinator and >1 trainers, run `jax.distributed.initialize` (the
    TCPStore-rendezvous analog — reference distributed/parallel.py:94,248;
    the KV store at PADDLE_MASTER plays the TCPStore role). Idempotent;
    no-op for single-process jobs."""
    world = int(os.environ.get("PADDLE_TRAINERS_NUM", "1"))
    master = os.environ.get("PADDLE_MASTER", "")
    if world <= 1 or not master:
        return False
    import jax

    try:
        jax.distributed.initialize(
            coordinator_address=master,
            num_processes=world,
            process_id=int(os.environ.get("PADDLE_TRAINER_ID", "0")),
        )
    except RuntimeError as e:
        # benign: someone (us or the user) initialized already — jax raises
        # "distributed.initialize should only be called once".
        msg = str(e).lower()
        if "once" not in msg and "already" not in msg:
            raise
    _start_heartbeat()
    return True


_hb_thread = None


def _start_heartbeat():
    """Beat every second so the launcher (and ElasticManager peers) can
    tell a HUNG worker from a live one — process liveness alone misses
    wedged collectives (reference: elastic/manager.py etcd heartbeat
    with TTL, master.py:234).

    Two transports: with PADDLE_ELASTIC_MASTER set, beats go to the
    launcher's cross-host membership registry (launch/master.py — the
    reference's ETCDMaster role, no shared filesystem needed); otherwise
    the single-host fallback touches PADDLE_HEARTBEAT_DIR/hb_<rank>."""
    global _hb_thread
    master_ep = os.environ.get("PADDLE_ELASTIC_MASTER")
    hb_dir = os.environ.get("PADDLE_HEARTBEAT_DIR")
    if (not master_ep and not hb_dir) or _hb_thread is not None:
        return
    import threading
    import time

    rank = get_rank()
    client = None
    if master_ep:
        from .launch.master import MembershipClient

        client = MembershipClient(master_ep)
    # master mode is EXCLUSIVE: beats go only to the registry, proving
    # the path needs no shared filesystem (the dir protocol remains the
    # standalone/legacy fallback)
    path = (os.path.join(hb_dir, f"hb_{rank}")
            if hb_dir and client is None else None)

    # a worker that exits CLEANLY must not look like a wedged one:
    # deregister / remove the beat so monitors stop tracking it
    import atexit

    def _tombstone():
        if client is not None:
            try:
                client.clear(rank)
            except OSError:
                pass
        if path:
            try:
                os.unlink(path)
            except OSError:
                pass

    atexit.register(_tombstone)

    from . import resilience

    def beat():
        while True:
            if client is not None:
                try:
                    # degraded-vs-dead: carry retry telemetry so the
                    # launcher can tell a retry-storming (but alive)
                    # rank from a wedged one (launch/master.py health)
                    n_recent = resilience.recent_failures(30.0)
                    client.beat(rank, degraded=n_recent > 0,
                                retries=n_recent)
                except OSError:
                    pass
            if path:
                try:
                    with open(path, "w") as f:
                        f.write(str(time.time()))
                except OSError:
                    pass
            time.sleep(1.0)

    _hb_thread = threading.Thread(target=beat, daemon=True)
    _hb_thread.start()


def get_rank(group=None):
    if group is not None:
        return group.rank
    try:
        import jax

        if jax.process_count() > 1:
            return jax.process_index()
    except Exception:  # ptlint: disable=PTL804 (no distributed runtime; env-var fallback follows)
        pass
    return int(os.environ.get("PADDLE_TRAINER_ID", "0"))


def get_world_size(group=None):
    if group is not None:
        return group.nranks
    try:
        import jax

        if jax.process_count() > 1:
            return jax.process_count()
    except Exception:  # ptlint: disable=PTL804 (no distributed runtime; env-var fallback follows)
        pass
    return int(os.environ.get("PADDLE_TRAINERS_NUM", "1"))


class ParallelEnv:
    """(reference: python/paddle/fluid/dygraph/parallel.py ParallelEnv)."""

    @property
    def rank(self):
        return get_rank()

    @property
    def world_size(self):
        return get_world_size()

    @property
    def local_rank(self):
        return int(os.environ.get("PADDLE_LOCAL_RANK", str(get_rank())))

    @property
    def dev_id(self):
        return self.local_rank

    @property
    def current_endpoint(self):
        eps = self.trainer_endpoints
        return eps[self.rank] if self.rank < len(eps) else ""

    @property
    def trainer_endpoints(self):
        eps = os.environ.get("PADDLE_TRAINER_ENDPOINTS", "")
        return eps.split(",") if eps else []

    @property
    def nranks(self):
        return get_world_size()
