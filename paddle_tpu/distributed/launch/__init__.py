"""Distributed job launcher — ``python -m paddle_tpu.distributed.launch``.

TPU-native re-design of the reference launcher
(reference: python/paddle/distributed/launch/main.py:18 `launch()`,
launch/controllers/collective.py:24 CollectiveController.build_pod).

The reference spawns one process per GPU and hands each a NCCL rendezvous
via PADDLE_TRAINER_ENDPOINTS.  On TPU the natural unit is one process per
HOST (each process owns all local chips; XLA drives ICI/DCN collectives),
so the launcher's job collapses to:

  1. set the env contract (PADDLE_TRAINER_ID / PADDLE_TRAINERS_NUM /
     PADDLE_MASTER / ...) for each worker process,
  2. point every worker at one coordinator (jax.distributed uses a
     KV-store at PADDLE_MASTER the way the reference uses TCPStore —
     reference: python/paddle/distributed/parallel.py:94),
  3. babysit the pod: stream logs, propagate failures, optionally
     restart (--max_restart, reference launch/controllers/controller.py).

Workers call `paddle_tpu.distributed.init_parallel_env()` which picks up
the contract and runs `jax.distributed.initialize` (multi-controller
SPMD bring-up) before building the global mesh.

For CPU-host testing, `--nproc_per_node N` on one node emulates N hosts
(JAX gloo collectives connect the processes).
"""
import argparse
import os
import signal
import socket
import subprocess
import sys
import time

__all__ = ["launch", "parse_args"]


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def parse_args(argv=None):
    p = argparse.ArgumentParser(
        prog="python -m paddle_tpu.distributed.launch",
        description="Launch a distributed paddle_tpu job.",
    )
    p.add_argument("--master", default=None,
                   help="coordinator host:port (default: auto on one node)")
    p.add_argument("--rank", type=int, default=0,
                   help="rank of this node (0..nnodes-1)")
    p.add_argument("--nnodes", type=int, default=1,
                   help="number of nodes in the job")
    p.add_argument("--nproc_per_node", type=int, default=1,
                   help="worker processes on this node (1 per TPU host is "
                        "the norm; >1 emulates a pod on CPU)")
    p.add_argument("--log_dir", default="log", help="per-rank log directory")
    p.add_argument("--job_id", default="default", help="job id for log names")
    p.add_argument("--devices", default=None,
                   help="restrict visible devices (sets TPU_VISIBLE_DEVICES; "
                        "one process per host only)")
    p.add_argument("--max_restart", type=int, default=0,
                   help="restart the pod up to N times on failure")
    p.add_argument("--elastic_level", type=int, default=0,
                   help="0: restart-only; >=1: elastic membership — "
                        "scale-IN (after 2 consecutive failed attempts, "
                        "re-form the pod over the surviving slots with "
                        "contiguous rank remap) AND scale-OUT (a "
                        "fleet.elastic.request_scale_out join request "
                        "tears the pod down and re-forms it with the "
                        "joiners admitted; workers resume from the "
                        "latest checkpoint) — reference "
                        "elastic/manager.py. Single-node pods only.")
    p.add_argument("--elastic_timeout", type=float, default=30.0,
                   help="seconds without a worker heartbeat before the "
                        "pod is declared hung and restarted")
    p.add_argument("--log_level", default="INFO")
    p.add_argument("training_script", help="script to run")
    p.add_argument("training_script_args", nargs=argparse.REMAINDER)
    return p.parse_args(argv)


def _worker_env(args, local_rank, master, nproc=None, mm_endpoint=None,
                attempt=0):
    nproc = nproc if nproc is not None else args.nproc_per_node
    world = args.nnodes * nproc
    rank = args.rank * nproc + local_rank
    env = dict(os.environ)
    env.update({
        "PADDLE_MASTER": master,
        "PADDLE_TRAINER_ID": str(rank),
        "PADDLE_TRAINERS_NUM": str(world),
        "PADDLE_LOCAL_RANK": str(local_rank),
        "PADDLE_LOCAL_SIZE": str(nproc),
        "PADDLE_NNODES": str(args.nnodes),
        "PADDLE_JOB_ID": args.job_id,
        "PADDLE_HEARTBEAT_DIR": os.path.join(args.log_dir, "hb"),
        "PADDLE_ELASTIC_TIMEOUT": str(args.elastic_timeout),
        # per-rank anomaly journal (resilience.py) lands next to the logs
        "PADDLE_LOG_DIR": args.log_dir,
        # pod incarnation: namespaces KV-collective keys so a restarted
        # pod can never collide with a previous incarnation's leftovers
        "PADDLE_POD_ATTEMPT": str(attempt),
    })
    if mm_endpoint:
        env["PADDLE_ELASTIC_MASTER"] = mm_endpoint
    if args.devices is not None:
        env["TPU_VISIBLE_DEVICES"] = args.devices
    return env


def _spawn_pod(args, master, nproc=None, mm=None, attempt=0):
    """Start nproc workers; local rank 0 inherits the console."""
    nproc = nproc if nproc is not None else args.nproc_per_node
    os.makedirs(args.log_dir, exist_ok=True)
    hb_dir = os.path.join(args.log_dir, "hb")
    os.makedirs(hb_dir, exist_ok=True)
    # clear stale beats from a previous attempt. join_* requests are NOT
    # touched: they are consumed only by launch() after counting, so a
    # request landing during a teardown window is admitted next round
    # instead of silently dropped.
    if mm is not None:
        mm.reset_beats()
    for f in os.listdir(hb_dir):
        if f.startswith("hb_"):
            try:
                os.unlink(os.path.join(hb_dir, f))
            except OSError:
                pass
    procs = []
    cmd = [sys.executable, args.training_script] + args.training_script_args
    for lr in range(nproc):
        env = _worker_env(args, lr, master, nproc,
                          mm_endpoint=mm.endpoint if mm else None,
                          attempt=attempt)
        rank = env["PADDLE_TRAINER_ID"]
        if lr == 0:
            out = None  # inherit
        else:
            # append so logs from failed attempts survive --max_restart
            out = open(os.path.join(
                args.log_dir, f"{args.job_id}.rank{rank}.log"), "a")
        procs.append((subprocess.Popen(
            cmd, env=env, stdout=out,
            stderr=subprocess.STDOUT if out else None), out))
    return procs


RC_SCALE_OUT = 97  # synthetic: pod torn down to admit joining workers


def _pending_joins(hb_dir):
    """join_* request files dropped by elastic.request_scale_out
    (reference: elastic/manager.py:127 — ETCDMaster re-ranks on node
    ARRIVAL; the heartbeat dir plays the etcd registry). Shared
    protocol lives in fleet/elastic.py."""
    from ..fleet.elastic import pending_join_files

    return pending_join_files(hb_dir)


def _stale_beats(mm, hb_dir, hb_timeout):
    """(name, age) of workers whose heartbeat exceeds hb_timeout — from
    the membership master when one is active (cross-host, no shared
    FS), else from the heartbeat directory's file mtimes."""
    if mm is not None:
        return [(f"rank {r}", age) for r, age in mm.peers()
                if age > hb_timeout]
    out = []
    now = time.time()
    try:
        beats = os.listdir(hb_dir)
    except OSError:
        beats = []
    for f in beats:
        if not f.startswith("hb_"):
            continue  # join_* requests are not heartbeats
        try:
            age = now - os.path.getmtime(os.path.join(hb_dir, f))
        except OSError:
            continue
        if age > hb_timeout:
            out.append((f, age))
    return out


def _wait_pod(procs, poll_s=0.2, hb_dir=None, hb_timeout=0.0,
              rank_base=0, watch_joins=False, mm=None):
    """Block until all exit ok or one fails (then kill the rest).

    A worker whose heartbeat goes stale for longer than hb_timeout is
    declared HUNG and fails the pod — liveness alone misses a worker
    wedged in a dead collective (reference: etcd heartbeat TTL,
    elastic/manager.py:234). Beats come from the membership master
    (`mm`, launch/master.py — cross-host) or the heartbeat dir
    fallback. Only workers that have beaten at least once are
    monitored, so non-paddle scripts that never call init_parallel_env
    are unaffected. With watch_joins, a pending join request tears the
    pod down with RC_SCALE_OUT so the caller can re-form it at the
    larger size (reference scale-out on node join)."""
    alive = {i: p for i, (p, _) in enumerate(procs)}
    failed_rc = 0
    degraded = set()   # ranks currently marked degraded (log transitions)
    while alive and not failed_rc:
        time.sleep(poll_s)
        if mm is not None:
            # degraded-vs-dead: a rank that beats but reports retry
            # storms is logged, not failed — only beat STALENESS (below)
            # kills the pod
            for r, h in mm.health().items():
                if h["degraded"] and r not in degraded:
                    degraded.add(r)
                    print(f"[launch] worker rank {r} DEGRADED "
                          f"({h['retries']} recent retries; still "
                          "beating — not restarting)",
                          file=sys.stderr, flush=True)
                elif not h["degraded"] and r in degraded:
                    degraded.discard(r)
                    print(f"[launch] worker rank {r} recovered "
                          "(retries subsided)",
                          file=sys.stderr, flush=True)
        if watch_joins and (
                (mm is not None and mm.pending_joins())
                or (hb_dir and _pending_joins(hb_dir))):
            failed_rc = RC_SCALE_OUT
            break
        for i, p in list(alive.items()):
            rc = p.poll()
            if rc is None:
                continue
            del alive[i]
            if rc != 0:
                failed_rc = rc
            else:
                # clean exit: drop the worker's beat so the staleness
                # monitor doesn't mistake "finished" for "wedged" (the
                # worker's own atexit does this too; SIGKILL'd-after-done
                # edge cases land here)
                if mm is not None:
                    mm.clear_rank(rank_base + i)
                if hb_dir:
                    try:
                        os.unlink(os.path.join(hb_dir,
                                               f"hb_{rank_base + i}"))
                    except OSError:
                        pass
        if not failed_rc and hb_timeout > 0 and (mm is not None or hb_dir):
            for name, age in _stale_beats(mm, hb_dir, hb_timeout):
                print(f"[launch] worker {name} heartbeat stale "
                      f"({age:.0f}s > {hb_timeout:.0f}s): pod hung",
                      file=sys.stderr, flush=True)
                failed_rc = 98  # synthetic "hung" exit code
                break
    for p in alive.values():
        p.send_signal(signal.SIGTERM)
    deadline = time.time() + 10
    for p in alive.values():
        try:
            p.wait(timeout=max(0.1, deadline - time.time()))
        except subprocess.TimeoutExpired:
            p.kill()
    for _, out in procs:
        if out:
            out.close()
    return failed_rc


def launch(argv=None):
    args = parse_args(argv)
    # the launcher is a supervisor, not the measured workload: under
    # PT_TELEMETRY=1 its own at-exit export would land on rank 0's
    # files (no PADDLE_TRAINER_ID here) and overwrite the worker's real
    # snapshot after the pod exits — drop to counting-only
    from ...observability import full_enabled, set_mode

    if full_enabled():
        set_mode("metrics")
    if args.training_script_args[:1] == ["--"]:
        args.training_script_args = args.training_script_args[1:]
    master = args.master
    if master is None:
        if args.nnodes > 1:
            sys.exit("--master is required when --nnodes > 1")
        master = f"127.0.0.1:{_free_port()}"
    if args.devices is not None and args.nproc_per_node > 1:
        # every local rank would get the SAME TPU_VISIBLE_DEVICES, and a
        # chip belongs to one process at a time: the second rank to
        # reach the chip fails or hangs
        sys.exit("--devices with --nproc_per_node > 1 hands every local "
                 "rank the same chips, and a chip belongs to one process "
                 "at a time: run one process per host over all its chips "
                 "(the default), or start one launcher per chip set")
    if args.elastic_level >= 1 and args.nnodes > 1:
        # membership (heartbeats/joins) is cross-host via the
        # MembershipMaster, but pod RE-FORMING at a new size is still
        # coordinated per launcher invocation — multi-node re-forms
        # would need the launchers themselves to rendezvous
        sys.exit("--elastic_level>=1 is single-node-pod scoped "
                 "(cross-host membership is available via "
                 "PADDLE_ELASTIC_MASTER, but pod re-forming is not "
                 "multi-node yet)")
    nproc = args.nproc_per_node
    hb_dir = os.path.join(args.log_dir, "hb")
    # Cross-host membership registry (reference ETCDMaster role): beats
    # and join requests flow through it, so elastic monitoring needs no
    # shared filesystem. PADDLE_TPU_MEMBERSHIP=dir forces the legacy
    # heartbeat-directory protocol.
    from .master import MembershipMaster

    # advertise an address routed toward the job coordinator so the
    # endpoint is reachable from other hosts (loopback when single-node)
    mm = (None if os.environ.get("PADDLE_TPU_MEMBERSHIP") == "dir"
          else MembershipMaster(
              route_via=master if args.nnodes > 1 else None))
    # join requests are only meaningful within ONE launch invocation —
    # a leftover from a previous job must not instantly tear down this
    # pod
    for path in _pending_joins(hb_dir):
        try:
            os.unlink(path)
        except OSError:
            pass
    consecutive = 0
    attempt = 0
    # pod incarnation counter: bumped on EVERY re-form (failure restart,
    # scale-in, scale-out) — unlike `attempt`, which only counts failures
    # toward --max_restart. It feeds PADDLE_POD_ATTEMPT, the epoch that
    # namespaces KV-collective keys, so no incarnation can ever read a
    # previous incarnation's leftover keys.
    pod_gen = -1
    rc = 1
    while True:
        pod_gen += 1
        procs = _spawn_pod(args, master, nproc, mm=mm, attempt=pod_gen)
        rc = _wait_pod(procs, hb_dir=hb_dir,
                       hb_timeout=args.elastic_timeout
                       if args.elastic_timeout > 0 else 0.0,
                       rank_base=args.rank * nproc,
                       watch_joins=args.elastic_level >= 1, mm=mm)
        if rc == 0:
            return 0
        n_joins = 0
        if args.elastic_level >= 1:
            join_files = _pending_joins(hb_dir)
            n_joins = len(join_files)
            if mm is not None:
                n_joins += mm.pending_joins()
        if rc == RC_SCALE_OUT and n_joins:
            # node join (reference ETCDMaster re-rank on peer arrival):
            # admit the joiners, re-form the pod at the larger size with
            # contiguous ranks; workers resume from the latest complete
            # checkpoint and re-shard their samplers at the new world
            # size. Not a failure: does not consume --max_restart.
            # Consume EXACTLY the counted requests — one that lands
            # between the count and the respawn survives for the next
            # watch round instead of being silently dropped.
            for path in join_files:
                try:
                    os.unlink(path)
                except OSError:
                    pass
            if mm is not None:
                mm.consume_joins(n_joins - len(join_files))
            nproc += n_joins
            consecutive = 0
            print(f"[launch] elastic scale-out: {n_joins} "
                  f"worker(s) joining; re-forming pod with {nproc} "
                  f"workers (ranks remapped 0..{nproc - 1})",
                  file=sys.stderr, flush=True)
            continue
        # a worker that genuinely exits 97 (without any join request)
        # falls through to the normal failure/restart path
        attempt += 1
        if attempt > args.max_restart:
            break
        print(f"[launch] pod failed; restart {attempt}/{args.max_restart}"
              f" (nproc={nproc})", file=sys.stderr, flush=True)
        consecutive += 1
        # elastic scale-in: the pod keeps dying at this size — re-form it
        # over the surviving slots with a contiguous rank remap
        # (reference elastic/manager.py:127 rank-map regeneration)
        if args.elastic_level >= 1 and consecutive >= 2 and nproc > 1:
            nproc -= 1
            consecutive = 0
            print(f"[launch] elastic scale-in: re-forming pod with "
                  f"{nproc} workers (ranks remapped 0..{nproc - 1})",
                  file=sys.stderr, flush=True)
    sys.exit(rc)
