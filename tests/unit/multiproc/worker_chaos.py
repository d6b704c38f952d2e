"""Chaos worker for the two-process comm-fault tests
(test_comm_chaos.py): a minimal jax.distributed worker (one CPU device
per process) that runs eager collectives under a per-rank injected
fault and must terminate DETERMINISTICALLY — fault detected, named in
output, clean nonzero exit — instead of hanging until the fixture
timeout.

Env: DSTPU_COORD (host:port), DSTPU_NPROC, DSTPU_PID, DSTPU_MODE
(corrupt | straggle | drop | kill), DSTPU_WD (collective watchdog
deadline seconds), plus the DSTPU_FAULT_SPEC / DSTPU_FAULT_RANK fault
plumbing (resilience/distributed.py install_injector_from_env).

Exit codes (asserted by the test):
  0  mode completed with nothing detected (a test FAILURE for corrupt)
  3  cross-rank desync detected (GradientAnomalyError)
  4  collective watchdog timeout (CollectiveTimeout)
  5  peer/transport failure surfaced as an ordinary exception
"""
import json
import os
import signal
import sys
import time

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 1)

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))))

from deepspeed_tpu.resilience.retry import retriable  # noqa: E402

retriable(attempts=4, base_s=0.5, cap_s=4.0,
          retry_on=(RuntimeError, OSError))(jax.distributed.initialize)(
    coordinator_address=os.environ["DSTPU_COORD"],
    num_processes=int(os.environ["DSTPU_NPROC"]),
    process_id=int(os.environ["DSTPU_PID"]))

import jax.numpy as jnp            # noqa: E402

import deepspeed_tpu.comm as dist  # noqa: E402
from deepspeed_tpu.comm import watchdog  # noqa: E402
from deepspeed_tpu.resilience import distributed as rdist  # noqa: E402
from deepspeed_tpu.resilience.distributed import (  # noqa: E402
    CollectiveTimeout, DesyncDetector)
from deepspeed_tpu.resilience.guards import GradientAnomalyError  # noqa: E402

EXIT_DESYNC = 3
EXIT_TIMEOUT = 4
EXIT_PEER = 5
EXIT_DROPPED = 6


def _exit(code: int) -> None:
    """Exit WITHOUT the jax.distributed shutdown barrier: on a fault
    abort the peer is (by design) dead or wedged, and the coordination
    service's shutdown handshake would either hang or SIGABRT the
    process ("Terminating process because the JAX distributed service
    detected fatal errors"), destroying the deterministic exit code the
    test asserts on."""
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)


def main() -> int:
    mode = os.environ["DSTPU_MODE"]
    pid = jax.process_index()
    watchdog.configure(float(os.environ.get("DSTPU_WD", "20")))
    rdist.install_injector_from_env()
    dist.initialize_mesh(dp=int(os.environ["DSTPU_NPROC"]))
    dist.comms_logger.enabled = True
    n = dist.get_world_size("data")
    x = jnp.stack([jnp.full((64,), 1.0) for _ in range(n)])

    try:
        if mode == "corrupt":
            # call 1 is clean (baseline equality must pass); the
            # injector corrupts rank 1's local view of call 2 and the
            # per-step desync check turns it into a loud abort
            det = DesyncDetector(interval=1)
            for step in (1, 2, 3):
                out = dist.all_reduce(x, group="data")
                det.check({"all_reduce": rdist.tree_checksum(out)}, step)
            print("RESULT " + json.dumps({"pid": pid, "detected": False}),
                  flush=True)
            return 0
        if mode == "straggle":
            # rank 1 arrives late on calls 2-4; the cross-rank report
            # must NAME it (peers wait, the straggler itself doesn't)
            for _ in range(4):
                dist.all_reduce(x, group="data")
            report = dist.straggler_report()
            print("RESULT " + json.dumps(
                {"pid": pid, "straggler": report.get("all_reduce")}),
                flush=True)
            print(dist.log_summary(show_straggler=True), flush=True)
            return 0
        if mode == "drop":
            dist.all_reduce(x, group="data")   # clean call (warms cache)
            dist.all_reduce(x, group="data")   # rank 1 drops: peers stall
            if pid == int(os.environ.get("DSTPU_FAULT_RANK", "-1")):
                # the dropper must stay OFF the transport: issuing any
                # further collective slams a mismatched op into the
                # stream the peer is still blocked on and gloo
                # std::terminate's the process.  Idle until the peer's
                # watchdog has long since fired, then exit marked.
                print(f"DROPPED rank={pid}: collective skipped; idling "
                      "while peers hit their watchdog deadline",
                      flush=True)
                time.sleep(3 * float(os.environ.get("DSTPU_WD", "20")))
                _exit(EXIT_DROPPED)
            dist.barrier()
            print("RESULT " + json.dumps({"pid": pid, "detected": False}),
                  flush=True)
            return 0
        if mode == "kill":
            dist.all_reduce(x, group="data")
            if pid == 1:
                print("KILLED rank=1 (SIGKILL mid-step)", flush=True)
                os.kill(os.getpid(), signal.SIGKILL)
            time.sleep(0.5)                    # let the kill land first
            dist.all_reduce(x, group="data")   # survivor stalls -> watchdog
            dist.barrier()
            print("RESULT " + json.dumps({"pid": pid, "detected": False}),
                  flush=True)
            return 0
        raise SystemExit(f"unknown DSTPU_MODE {mode!r}")
    except GradientAnomalyError as e:
        print(f"DESYNC_DETECTED rank={pid}: {e}", flush=True)
        _exit(EXIT_DESYNC)
    except CollectiveTimeout as e:
        print(f"COLLECTIVE_TIMEOUT rank={pid}: {e}", flush=True)
        _exit(EXIT_TIMEOUT)
    except Exception as e:
        print(f"COMM_PEER_FAILURE rank={pid}: {type(e).__name__}: {e}",
              flush=True)
        _exit(EXIT_PEER)


if __name__ == "__main__":
    sys.exit(main())
