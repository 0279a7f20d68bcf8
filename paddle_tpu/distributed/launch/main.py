"""Process launcher (ref: python/paddle/distributed/launch/main.py:23
launch(); controllers/collective.py:37 build_pod; env contract set at
collective.py:76-132).

TPU-native shape: jax is single-controller per HOST (one process drives
all local chips), so the per-GPU-process fan-out the reference performs
collapses to one worker per node; multi-node rendezvous goes through the
jax coordination service (PADDLE_MASTER -> coordinator_address) instead
of TCPStore. The reference's env contract is preserved so existing
`paddle.distributed.launch`-style scripts keep working:

    python -m paddle_tpu.distributed.launch --nnodes=2 \
        --master=10.0.0.1:8090 --rank=0 train.py --my-args

Workers read PADDLE_TRAINER_ID / PADDLE_TRAINERS_NUM / PADDLE_MASTER
(ParallelEnv, distributed/parallel.py) and call
paddle.distributed.init_parallel_env().
"""
from __future__ import annotations

import argparse
import os
import signal
import subprocess
import sys
import time

from ...compilecache import cache_root
from ...resilience.train_state import HANG_EXIT_CODE, PREEMPT_EXIT_CODE

__all__ = ["launch"]


def _parse(argv):
    p = argparse.ArgumentParser(
        prog="paddle_tpu.distributed.launch",
        description="Launch distributed training workers",
    )
    p.add_argument("--nnodes", type=int, default=1,
                   help="number of nodes (hosts)")
    p.add_argument("--nproc_per_node", type=int, default=1,
                   help="worker processes per node (TPU: 1 process "
                        "drives all local chips)")
    p.add_argument("--master", type=str, default=None,
                   help="coordinator host:port (node rank 0)")
    p.add_argument("--rank", type=int,
                   default=int(os.environ.get("PADDLE_NODE_RANK", 0)),
                   help="this node's rank")
    p.add_argument("--log_dir", type=str, default="log",
                   help="per-worker log directory")
    p.add_argument("--devices", type=str, default=None,
                   help="visible device ids (comma separated)")
    p.add_argument("--max_restarts", type=int, default=0,
                   help="elastic: relaunch the pod up to N times after a "
                        "worker failure (workers resume from their own "
                        "checkpoints; PADDLE_RESTART_COUNT tells them "
                        "which incarnation they are). A pod that exits "
                        f"{PREEMPT_EXIT_CODE} (preemption after a "
                        "verified emergency checkpoint) relaunches "
                        "WITHOUT consuming this budget")
    p.add_argument("--max_preempt_restarts", type=int, default=100,
                   help="runaway guard: bound preemption relaunches "
                        "(which never burn --max_restarts) so a worker "
                        "stuck in a preempt-exit loop cannot respawn "
                        "forever")
    p.add_argument("--restart_interval", type=float, default=1.0,
                   help="seconds between elastic relaunches")
    p.add_argument("--elastic", action="store_true",
                   help="elastic manager v2: store-based membership with "
                        "rank remap — on any node's failure the surviving "
                        "nodes re-rendezvous, get new contiguous ranks "
                        "(scale-down) and relaunch; requires --master")
    p.add_argument("--elastic_grace", type=float, default=5.0,
                   help="seconds the master waits for members to register "
                        "before sealing a (possibly smaller) RE-rendezvous "
                        "epoch")
    p.add_argument("--elastic_join_timeout", type=float, default=300.0,
                   help="seconds the master waits for the FULL node set "
                        "at the initial (epoch 0) rendezvous")
    p.add_argument("training_script", type=str)
    p.add_argument("training_script_args", nargs=argparse.REMAINDER)
    return p.parse_args(argv)


def _worker_env(args, local_rank, node_rank=None, nnodes=None,
                master=None):
    env = dict(os.environ)
    node_rank = args.rank if node_rank is None else node_rank
    nnodes = args.nnodes if nnodes is None else nnodes
    master = args.master if master is None else master
    world = nnodes * args.nproc_per_node
    rank = node_rank * args.nproc_per_node + local_rank
    env.update({
        "PADDLE_TRAINER_ID": str(rank),
        "PADDLE_TRAINERS_NUM": str(world),
        "PADDLE_LOCAL_RANK": str(local_rank),
        "PADDLE_NNODES": str(nnodes),
    })
    if master:
        env["PADDLE_MASTER"] = master
        # jax.distributed.initialize reads these directly
        env["JAX_COORDINATOR_ADDRESS"] = master
        env["JAX_NUM_PROCESSES"] = str(world)
        env["JAX_PROCESS_ID"] = str(rank)
    if args.devices:
        env["TPU_VISIBLE_DEVICES"] = args.devices
    # the worker is the user's script: hand it the compile-cache root
    # through the variables jax itself reads at import
    env["JAX_COMPILATION_CACHE_DIR"] = cache_root()
    env.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    return env


def launch(argv=None):
    """Run the pod; with --max_restarts > 0, relaunch it after worker
    failures (the elastic policy).

    ref: fleet/elastic/manager.py:125 — the reference's elastic manager
    watches etcd membership and rebuilds the pod on change. The TPU
    single-controller form needs no external store: the pod IS the
    membership (one process per host over the jax coordination service),
    so elasticity reduces to supervised relaunch — each incarnation gets
    PADDLE_RESTART_COUNT and resumes from its sharded checkpoint
    (distributed/checkpoint.py), which is the reference's
    train-resume contract."""
    args = _parse(argv if argv is not None else sys.argv[1:])
    if args.elastic:
        return _elastic_launch(args)
    restarts = 0    # crash budget consumed (--max_restarts)
    preempts = 0    # preemption relaunches (budget-free)
    history = []    # (incarnation, exit code) for the summary
    reason = None   # restart provenance handed to the NEXT incarnation
    while True:
        incarnation = restarts + preempts
        code = _run_pod(args, incarnation, restart_reason=reason)
        history.append((incarnation, code))
        if code in (0, 130):
            _pod_summary(history)
            return code
        if code == PREEMPT_EXIT_CODE:
            # preemption protocol (resilience.train_state): the worker
            # checkpointed and exited on a preemption notice — relaunch
            # without burning the crash budget
            if preempts >= args.max_preempt_restarts:
                print(
                    f"elastic: max_preempt_restarts "
                    f"({args.max_preempt_restarts}) exhausted",
                    file=sys.stderr,
                )
                _pod_summary(history)
                return code
            preempts += 1
            reason = "preempt"
            print(
                f"elastic: pod preempted (emergency checkpoint taken); "
                f"relaunching (preempt {preempts}, crash budget "
                f"untouched at {restarts}/{args.max_restarts}) in "
                f"{args.restart_interval}s",
                file=sys.stderr,
            )
        else:
            if restarts >= args.max_restarts:
                _pod_summary(history)
                return code
            restarts += 1
            reason = "crash"
            print(
                f"elastic: relaunching pod (restart {restarts}/"
                f"{args.max_restarts}) in {args.restart_interval}s",
                file=sys.stderr,
            )
        time.sleep(args.restart_interval)


def _classify_exit(code):
    if code == 0:
        return "ok"
    if code == PREEMPT_EXIT_CODE:
        return "preempt"
    if code == HANG_EXIT_CODE:
        # watchdog-detected stuck step: burns the crash budget like any
        # failure, but the summary should say what actually happened
        return "hang"
    if code == 130:
        return "interrupt"
    return "crash"


def _pod_summary(history):
    """Per-incarnation exit codes, printed once at launcher exit so a
    postmortem reads the whole restart history in one place."""
    if not history:
        return
    print("launch summary:", file=sys.stderr)
    for incarnation, code in history:
        print(
            f"  incarnation {incarnation}: exit={code} "
            f"({_classify_exit(code)})",
            file=sys.stderr,
        )


_RESTART_CODE = -999  # internal: pod stopped because the epoch moved on


def _elastic_launch(args):
    """Elastic manager v2 (ref fleet/elastic/manager.py:125): membership
    epochs over the TCPStore. Per epoch every surviving node registers;
    the master seals the member list after a grace period (all nnodes
    present ends the wait early), assigns NEW CONTIGUOUS RANKS (rank
    remap — a lost node shrinks the world), and every node launches its
    pod against a fresh coordinator port. Any node whose pod fails bumps
    the epoch; every supervision loop polls it and re-rendezvouses.
    Workers see the usual env contract plus PADDLE_RESTART_COUNT and
    resume from their checkpoints."""
    import json as _json

    from ..store import TCPStore

    if not args.master:
        raise SystemExit("--elastic requires --master host:port")
    host, port = args.master.rsplit(":", 1)
    store = TCPStore(
        host, int(port) + 1, is_master=args.rank == 0, timeout=120.0
    )
    epoch, restarts, preempts, incarnation = 0, 0, 0, 0
    reason = None
    history = []
    while True:
        epoch = max(
            epoch, int(store.get("current_epoch", wait=False) or 0)
        )
        store.set(f"epoch/{epoch}/node/{args.rank}", "alive")
        if args.rank == 0:
            # epoch 0 is the initial rendezvous: wait for the FULL node
            # set (the reference's job-start join); re-rendezvous epochs
            # use the short grace and seal with the survivors
            wait = (args.elastic_join_timeout if epoch == 0
                    else args.elastic_grace)
            deadline = time.time() + wait
            while time.time() < deadline:
                n = len(store.list_keys(f"epoch/{epoch}/node/"))
                if n >= args.nnodes:
                    break
                time.sleep(0.1)
            members = sorted(
                int(k.rsplit("/", 1)[1])
                for k in store.list_keys(f"epoch/{epoch}/node/")
            )
            plan = {
                "ranks": {str(nid): i for i, nid in enumerate(members)},
                "nnodes": len(members),
                "coord_port": int(port) + 2 + epoch,
            }
            store.set(f"epoch/{epoch}/plan", _json.dumps(plan))
            print(f"elastic: epoch {epoch} sealed with nodes {members}",
                  file=sys.stderr)
        # the master seals epoch 0 only after --elastic_join_timeout, so
        # non-master nodes must out-wait that window (store default is
        # 120s; a straggler sealing late would otherwise kill the others)
        plan = _json.loads(store.get(
            f"epoch/{epoch}/plan",
            timeout=args.elastic_join_timeout + 60.0,
        ))
        my_rank = plan["ranks"].get(str(args.rank))
        if my_rank is None:
            print(f"elastic: node {args.rank} not in epoch {epoch}; "
                  "exiting", file=sys.stderr)
            return 0

        def epoch_moved(e=epoch):
            return int(store.get("current_epoch", wait=False) or 0) > e

        code = _run_pod(
            args, incarnation, node_rank=my_rank, nnodes=plan["nnodes"],
            master=f"{host}:{plan['coord_port']}", stop_check=epoch_moved,
            restart_reason=reason,
        )
        if code != _RESTART_CODE:
            history.append((incarnation, code))
        if code == 0:
            _pod_summary(history)
            return 0
        if code == PREEMPT_EXIT_CODE:
            # preempted node: checkpointed; rejoin the next epoch
            # without consuming the crash budget — but under the same
            # runaway guard as the non-elastic path
            if preempts >= args.max_preempt_restarts:
                print(
                    f"elastic: max_preempt_restarts "
                    f"({args.max_preempt_restarts}) exhausted",
                    file=sys.stderr,
                )
                _pod_summary(history)
                return code
            preempts += 1
            incarnation += 1
            reason = "preempt"
            store.set("current_epoch", str(epoch + 1))
        elif code != _RESTART_CODE:
            # our pod failed: tell the others and count the restart
            restarts += 1
            incarnation += 1
            reason = "crash"
            store.set("current_epoch", str(epoch + 1))
            if restarts > args.max_restarts:
                print(f"elastic: max_restarts ({args.max_restarts}) "
                      "exhausted", file=sys.stderr)
                _pod_summary(history)
                return code
        epoch += 1
        time.sleep(args.restart_interval)


def _run_pod(args, restart_count=0, node_rank=None, nnodes=None,
             master=None, stop_check=None, restart_reason=None):
    os.makedirs(args.log_dir, exist_ok=True)

    procs = []
    for local_rank in range(args.nproc_per_node):
        nr = args.rank if node_rank is None else node_rank
        rank = nr * args.nproc_per_node + local_rank
        suffix = f".r{restart_count}" if restart_count else ""
        log_path = os.path.join(args.log_dir, f"workerlog.{rank}{suffix}")
        log_f = open(log_path, "w")
        cmd = [sys.executable, "-u", args.training_script,
               *args.training_script_args]
        env = _worker_env(args, local_rank, node_rank=node_rank,
                          nnodes=nnodes, master=master)
        env["PADDLE_RESTART_COUNT"] = str(restart_count)
        # restart provenance: preempt|crash next to the incarnation
        # count, so a resuming worker can tell a budget-free preemption
        # relaunch from a crash recovery (first incarnations get none)
        env.pop("PADDLE_RESTART_REASON", None)
        if restart_reason is not None:
            env["PADDLE_RESTART_REASON"] = restart_reason
        proc = subprocess.Popen(
            cmd, env=env,
            stdout=log_f, stderr=subprocess.STDOUT,
        )
        procs.append((proc, log_f, log_path))
        print(f"launched worker rank={rank} pid={proc.pid} "
              f"log={log_path}", file=sys.stderr)

    # Pod supervision (ref controllers/watcher.py): fail fast if any
    # worker dies nonzero, terminate the rest.
    exit_code = 0
    try:
        while procs:
            if stop_check is not None and stop_check():
                print("elastic: epoch moved on — stopping local pod",
                      file=sys.stderr)
                _terminate(procs)
                return _RESTART_CODE
            alive = []
            for proc, log_f, log_path in procs:
                ret = proc.poll()
                if ret is None:
                    alive.append((proc, log_f, log_path))
                    continue
                log_f.close()
                if ret != 0:
                    print(
                        f"worker pid={proc.pid} exited {ret}; see "
                        f"{log_path} — terminating pod",
                        file=sys.stderr,
                    )
                    exit_code = ret
                    _terminate(alive + procs[procs.index((proc, log_f,
                                                          log_path)) + 1:])
                    procs = []
                    alive = []
                    break
            procs = alive
            if procs:
                time.sleep(0.2)
    except KeyboardInterrupt:
        _terminate(procs)
        exit_code = 130
    return exit_code


def _terminate(procs, grace=5.0):
    """SIGTERM the pod, wait out the grace period, SIGKILL stragglers,
    and close log handles (workers must not outlive the launcher and keep
    the TPU locked for the next job)."""
    for proc, _, _ in procs:
        if proc.poll() is None:
            proc.send_signal(signal.SIGTERM)
    deadline = time.time() + grace
    for proc, log_f, _ in procs:
        remaining = max(0.1, deadline - time.time())
        try:
            proc.wait(timeout=remaining)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()
        try:
            log_f.close()
        except OSError:
            pass  # flush of a torn log pipe; the procs are already down


if __name__ == "__main__":
    sys.exit(launch())
