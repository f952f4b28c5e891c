#!/usr/bin/env python
"""Crash supervisor: keep a training run alive across crashes.

The trainer recovers from bad BATCHES in-process (train/anomaly.py) and
writes a resumable rescue checkpoint on catchable exits (trainer.py),
but a hard crash — SIGKILL preemption, OOM kill, a segfaulting runtime —
needs an outside process to relaunch it. This wrapper is that process:

  python tools/train_supervisor.py --resume-ckpt runs/exp.last.ckpt \
      --max-restarts 5 --restart-log runs/restarts.json -- \
      python train.py --checkpoint-path runs/exp.ckpt ...

Behavior:
  - Runs the child command verbatim first. On an ABNORMAL exit it
    relaunches with ``--resume-from <resume-ckpt>`` injected (replacing
    any existing ``--resume-from``) when that checkpoint VERIFIES,
    after an exponential backoff (``backoff_base * 2^restart``, capped),
    up to ``--max-restarts`` relaunches.
  - Verified resume: ``--resume-ckpt`` may be a checkpoint dir or the
    root of a rotating ``step-*`` tree. Integrity manifests
    (train/ckpt_writer.py, spec-loaded by file path so no jax is
    imported) are checked before injecting: a tree resolves to the
    NEWEST step checkpoint whose digests verify, falling back to older
    ones; a single dir must verify (a corrupt or manifest-less one is
    skipped and logged — the child may still resolve its own via
    ``--resume-from auto``, and a pre-manifest dir can be certified
    with ``tools/ckpt_doctor.py --adopt-legacy``). A crash mid-save
    can therefore never wedge the restart loop on a half-written
    checkpoint.
  - Exit classification: rc 0 is a CLEAN exit (done — this includes the
    trainer's SIGTERM graceful stop, which exits 0 after its rescue
    save); rc ``HANG_EXIT_CODE`` (113) is a step-deadline watchdog
    fire (train/watchdog.py) — a HANG, restartable like a crash but
    against its own ``--max-hang-restarts`` budget; death BY SIGTERM
    without the graceful handler is a preemption — the supervisor
    stops by default (the scheduler is taking the host;
    ``--restart-on-sigterm`` opts into relaunching); anything else is
    a CRASH and is restarted.
  - Elastic relaunch (``--elastic``): before each relaunch the
    surviving accelerator count is probed (a jax subprocess, or the
    ``--elastic-probe`` command) and the child's ``--data-parallel``
    is resized so the mesh fits it — the Cloud-TPU preemption that
    returns a smaller slice resumes on what came back instead of
    waiting forever. Pair with the child's ``--resume-from auto``:
    checkpoints are host-canonical, so the resume reshards exactly
    (train/checkpoint.py:elastic_resume_info).
  - SIGTERM/SIGINT to the supervisor are forwarded to the child and end
    the loop after the child exits (no restart).
  - Every launch appends one JSON record to ``--restart-log``
    (JSON-lines: time, attempt, argv, rc, outcome, duration, what it
    resumed from), the audit trail for flaky-host forensics.
  - Fault-injection specs (utils/faults.py) in the child's DTX_FAULTS
    env are stripped on restarts unless ``--keep-faults``: the harness
    injects a fault ONCE to test this very supervisor; replaying it on
    the resumed run would kill every relaunch at the same step.

No jax import here — the supervisor must stay alive when the runtime it
babysits is the thing crashing.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import time
from typing import List, Optional

FAULTS_ENV = "DTX_FAULTS"
# Exit status of a step-deadline watchdog fire — kept in sync with
# train/watchdog.py:HANG_EXIT_CODE (not imported: that module lives in
# the jax-importing package this supervisor must outlive; the value is
# part of the trainer<->supervisor contract like a signal number).
HANG_EXIT_CODE = 113

# mesh-axis flags train.py understands; --elastic rewrites the data
# axis so the product fits the surviving device count
_MESH_FLAGS = ("--data-parallel", "--fsdp", "--tensor-parallel",
               "--sequence-parallel", "--pipeline-parallel")


def _ckpt_tools():
    """train/ckpt_writer.py loaded BY FILE PATH: its module scope is
    stdlib-only, so manifest verification works here without importing
    the package (whose __init__ chain would pull jax — the runtime this
    supervisor must outlive). None when the file is missing (repo
    layout changed): callers degrade to the legacy existence check."""
    import importlib.util

    path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "..",
        "differential_transformer_replication_tpu", "train",
        "ckpt_writer.py",
    )
    try:
        spec = importlib.util.spec_from_file_location(
            "_supervisor_ckpt_writer", path
        )
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod
    except Exception as e:  # noqa: BLE001
        print(f"train_supervisor: checkpoint verification unavailable "
              f"({e!r}); falling back to existence checks",
              file=sys.stderr)
        return None


def resolve_resume_ckpt(path: Optional[str], ckpt=None) -> Optional[str]:
    """The checkpoint dir to inject as ``--resume-from``, or None.

    ``path`` is a checkpoint dir or a rotating-tree root; ``ckpt`` is
    the (possibly None) ckpt_writer module. Only a checkpoint that
    passes manifest verification is injected — newest-first with
    fallback across a tree — so the child never restarts into a
    half-written or bit-rotted save."""
    if not path:
        return None
    if ckpt is None:
        ckpt = _ckpt_tools()
    if ckpt is None:  # degraded mode: the pre-manifest behavior
        return path if os.path.isfile(
            os.path.join(path, "state.msgpack")
        ) else None
    if ckpt.list_step_checkpoints(path):
        resolved, skipped = ckpt.latest_verified_checkpoint(path)
        for p, why in skipped:
            print(f"train_supervisor: skipping unverified checkpoint "
                  f"{p}: {why}", file=sys.stderr)
        return resolved
    if os.path.exists(os.path.join(path, ckpt.MANIFEST_NAME)):
        if ckpt.is_verified(path):
            return path
        print(f"train_supervisor: checkpoint {path} fails integrity "
              "verification; not injecting --resume-from",
              file=sys.stderr)
        return None
    if os.path.isfile(os.path.join(path, "state.msgpack")):
        # manifest-less legacy dir: the trainer's verified load would
        # reject it on every relaunch — injecting it would wedge the
        # restart loop on a CheckpointError, the exact failure this
        # resolution exists to prevent
        print(f"train_supervisor: checkpoint {path} has no integrity "
              "manifest; not injecting --resume-from (certify it with "
              "tools/ckpt_doctor.py --adopt-legacy)", file=sys.stderr)
    return None


def classify_exit(rc: int) -> str:
    """clean / hang / sigterm / sigkill / crash from a subprocess
    returncode (negative rc = death by that signal; 128+N covers
    shells that re-report signal deaths as exit codes). ``hang`` is
    the step-deadline watchdog's distinct exit (train/watchdog.py): a
    wedged step, restartable like a crash but budgeted separately —
    a flaky host that hangs repeatedly must not eat the crash budget
    a genuinely flaky run needs (and vice versa)."""
    if rc == 0:
        return "clean"
    if rc == HANG_EXIT_CODE:
        return "hang"
    sig = -rc if rc < 0 else (rc - 128 if 128 < rc < 160 else None)
    if sig == signal.SIGTERM:
        return "sigterm"
    if sig == signal.SIGKILL:
        return "sigkill"
    return "crash"


def _strip_flag(cmd: List[str], flag: str) -> List[str]:
    """Drop ``flag X`` / ``flag=X`` occurrences from an argv list."""
    out = []
    skip = False
    for a in cmd:
        if skip:
            skip = False
            continue
        if a == flag:
            skip = True
            continue
        if a.startswith(flag + "="):
            continue
        out.append(a)
    return out


def with_resume(cmd: List[str], ckpt: str) -> List[str]:
    """Inject ``--resume-from <ckpt>``, replacing an existing flag (both
    ``--resume-from X`` and ``--resume-from=X`` forms)."""
    return _strip_flag(cmd, "--resume-from") + ["--resume-from", ckpt]


def _flag_value(cmd: List[str], flag: str, default: int = 1) -> int:
    """Last value of an integer ``flag X`` / ``flag=X`` in an argv list
    (train.py semantics: argparse keeps the last occurrence)."""
    val = default
    for i, a in enumerate(cmd):
        if a == flag and i + 1 < len(cmd):
            try:
                val = int(cmd[i + 1])
            except ValueError:
                pass
        elif a.startswith(flag + "="):
            try:
                val = int(a.split("=", 1)[1])
            except ValueError:
                pass
    return val


def probe_device_count(probe_cmd: Optional[List[str]] = None,
                       env: Optional[dict] = None,
                       timeout: float = 300.0) -> Optional[int]:
    """The accelerator count a relaunched child would see, probed in a
    SUBPROCESS (this supervisor never imports jax itself — the runtime
    it babysits is the thing that crashes). The default probe asks jax
    in the child's environment; ``--elastic-probe`` overrides it (and
    makes chaos tests deterministic). None on any failure — the caller
    then relaunches with the mesh flags untouched. Probe only BETWEEN
    children: a chip belongs to one process at a time, so beside a live
    training child the probe would fail or hang."""
    cmd = probe_cmd or [
        sys.executable, "-c", "import jax; print(jax.device_count())"
    ]
    try:
        out = subprocess.run(cmd, capture_output=True, text=True,
                             timeout=timeout, env=env)
        return int(out.stdout.strip().splitlines()[-1])
    except (OSError, ValueError, IndexError,
            subprocess.TimeoutExpired):
        return None


def with_elastic_mesh(cmd: List[str], n_devices: int) -> List[str]:
    """SHRINK the child's ``--data-parallel`` so the mesh-axis product
    fits ``n_devices`` — the elastic relaunch after a preemption
    returned a smaller slice. Only the data axis is resized (it is the
    one axis whose extent never changes parameter shapes, so the
    host-canonical checkpoint reshards exactly; shrinking fsdp/tensor/
    sequence/pipeline re-partitions math the operator chose
    deliberately). A mesh that ALREADY fits is returned unchanged —
    elastic means "run on what survived", never "grab every device":
    an operator who under-subscribed on purpose (batch divisibility,
    devices reserved for something else) must not be silently
    retopologized by a restart. When the non-data axes alone exceed
    the surviving devices the argv is also unchanged — the child
    fails loudly with create_mesh's clear error rather than silently
    training a different topology than asked."""
    other = 1
    for flag in _MESH_FLAGS:
        if flag != "--data-parallel":
            other *= _flag_value(cmd, flag)
    if other > n_devices:
        return cmd
    if _flag_value(cmd, "--data-parallel") * other <= n_devices:
        return cmd  # already fits: never upsize
    new_data = max(1, n_devices // other)
    return _strip_flag(cmd, "--data-parallel") + [
        "--data-parallel", str(new_data)
    ]


def backoff_s(restart: int, base: float, cap: float) -> float:
    return min(base * (2 ** restart), cap)


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter
    )
    p.add_argument("--resume-ckpt", default=None,
                   help="checkpoint dir — or root of a rotating step-* "
                        "tree — to resume from on restarts (point it at "
                        "the run's last/rescue checkpoint or its .steps "
                        "dir); only a checkpoint passing integrity "
                        "verification is injected, newest first")
    p.add_argument("--max-restarts", type=int, default=5,
                   help="restart budget for crash-class exits; "
                        "exhausted -> exit with the child's last "
                        "returncode")
    p.add_argument("--max-hang-restarts", type=int, default=None,
                   help="separate restart budget for watchdog hang "
                        f"exits (rc {HANG_EXIT_CODE}, "
                        "train/watchdog.py); default: same value as "
                        "--max-restarts, counted independently")
    p.add_argument("--elastic", action="store_true",
                   help="before each relaunch, probe the surviving "
                        "accelerator count and rewrite the child's "
                        "--data-parallel so the mesh fits it — the "
                        "preemption-returned-a-smaller-slice case; "
                        "pair with the child's --resume-from auto "
                        "(checkpoints are host-canonical, so the "
                        "resume reshards exactly)")
    p.add_argument("--elastic-probe", default=None, metavar="CMD",
                   help="override the device-count probe command "
                        "(default: ask jax in a subprocess with the "
                        "child's env); the command's last stdout line "
                        "must be an integer")
    p.add_argument("--backoff-base", type=float, default=2.0,
                   help="first-restart backoff seconds (doubles per "
                        "restart)")
    p.add_argument("--backoff-max", type=float, default=120.0,
                   help="backoff cap in seconds")
    p.add_argument("--restart-log", default=None,
                   help="append one JSON record per launch to this file")
    p.add_argument("--restart-on-sigterm", action="store_true",
                   help="also restart after a SIGTERM death (default: a "
                        "preemption means stop)")
    p.add_argument("--keep-faults", action="store_true",
                   help="keep DTX_FAULTS in the child env on restarts "
                        "(default: first launch only)")
    p.add_argument("command", nargs=argparse.REMAINDER,
                   help="-- then the training command to supervise")
    return p


def _log(path: Optional[str], record: dict) -> None:
    if not path:
        return
    with open(path, "a") as f:
        f.write(json.dumps(record) + "\n")


def supervise(args: argparse.Namespace) -> int:
    cmd = list(args.command)
    if cmd and cmd[0] == "--":
        cmd = cmd[1:]
    if not cmd:
        print("train_supervisor: no command given (put it after --)",
              file=sys.stderr)
        return 2

    child: dict = {"proc": None}
    got_signal: dict = {"sig": None}

    def forward(signum, frame):
        del frame
        got_signal["sig"] = signum
        proc = child["proc"]
        if proc is not None and proc.poll() is None:
            proc.send_signal(signum)

    for s in (signal.SIGTERM, signal.SIGINT):
        signal.signal(s, forward)

    restarts = 0
    # hang (watchdog) restarts are budgeted separately from crash-class
    # ones: a host that keeps wedging and a run that keeps crashing are
    # different pathologies with different budgets
    class_restarts = {"hang": 0, "crash": 0}
    hang_budget = (
        args.max_hang_restarts if args.max_hang_restarts is not None
        else args.max_restarts
    )
    rc = 1
    while True:
        launch_cmd = cmd
        resumed_from = None
        elastic_devices = None
        env = None  # inherit
        if restarts > 0:
            ckpt = resolve_resume_ckpt(args.resume_ckpt)
            if ckpt:
                launch_cmd = with_resume(cmd, ckpt)
                resumed_from = ckpt
            if not args.keep_faults:
                # faults are first-launch-only through BOTH channels —
                # a --faults flag left in argv would re-fire the same
                # kill on every relaunch, exhausting the budget on the
                # exact replay hazard the env-strip exists to prevent
                launch_cmd = _strip_flag(launch_cmd, "--faults")
                if FAULTS_ENV in os.environ:
                    env = dict(os.environ)
                    del env[FAULTS_ENV]
            if args.elastic:
                # elastic relaunch: the slice that comes back after a
                # preemption may be smaller — resize the data axis to
                # the surviving device count so the relaunch runs
                # instead of waiting for hardware that will not return
                import shlex

                probe = (
                    shlex.split(args.elastic_probe)
                    if args.elastic_probe else None
                )
                elastic_devices = probe_device_count(probe, env=env)
                if elastic_devices:
                    resized = with_elastic_mesh(launch_cmd,
                                                elastic_devices)
                    if resized != launch_cmd:
                        print(f"train_supervisor: elastic relaunch on "
                              f"{elastic_devices} device(s): "
                              f"--data-parallel -> "
                              f"{_flag_value(resized, '--data-parallel')}",
                              file=sys.stderr)
                    launch_cmd = resized
                else:
                    print("train_supervisor: elastic device probe "
                          "failed; relaunching with the original mesh",
                          file=sys.stderr)
        t0 = time.time()
        child["proc"] = subprocess.Popen(launch_cmd, env=env)
        rc = child["proc"].wait()
        child["proc"] = None
        outcome = classify_exit(rc)
        _log(args.restart_log, {
            "time": time.strftime("%Y-%m-%dT%H:%M:%S%z"),
            "attempt": restarts,
            "argv": launch_cmd,
            "rc": rc,
            "outcome": outcome,
            "duration_s": round(time.time() - t0, 3),
            "resumed_from": resumed_from,
            "elastic_devices": elastic_devices,
        })
        if outcome == "clean":
            return 0
        if got_signal["sig"] is not None:
            print(f"train_supervisor: stopping (received signal "
                  f"{got_signal['sig']}; child exited {rc})", file=sys.stderr)
            return 128 + got_signal["sig"]
        if outcome == "sigterm" and not args.restart_on_sigterm:
            print("train_supervisor: child died by SIGTERM (preemption); "
                  "not restarting (use --restart-on-sigterm to override)",
                  file=sys.stderr)
            return 128 + signal.SIGTERM
        restart_class = "hang" if outcome == "hang" else "crash"
        budget = hang_budget if restart_class == "hang" else args.max_restarts
        if class_restarts[restart_class] >= budget:
            print(f"train_supervisor: {restart_class} restart budget "
                  f"exhausted ({budget}); last outcome {outcome} (rc {rc})",
                  file=sys.stderr)
            return rc if rc > 0 else 128 + (-rc)
        class_restarts[restart_class] += 1
        delay = backoff_s(restarts, args.backoff_base, args.backoff_max)
        print(f"train_supervisor: child {outcome} (rc {rc}); "
              f"{restart_class} restart "
              f"{class_restarts[restart_class]}/{budget} in {delay:.1f}s",
              file=sys.stderr)
        # interruptible backoff: a SIGTERM/SIGINT arriving here (child
        # gone, nothing to forward to) must stop the supervisor, not be
        # swallowed by a PEP 475-resumed sleep and followed by a fresh
        # hours-long run the operator never gets to signal again
        end = time.time() + delay
        while time.time() < end and got_signal["sig"] is None:
            time.sleep(min(0.1, max(0.0, end - time.time())))
        if got_signal["sig"] is not None:
            print(f"train_supervisor: stopping (received signal "
                  f"{got_signal['sig']} during backoff)", file=sys.stderr)
            return 128 + got_signal["sig"]
        restarts += 1


def main() -> None:
    sys.exit(supervise(build_parser().parse_args()))


if __name__ == "__main__":
    main()
