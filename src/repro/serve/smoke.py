"""Serve smoke drill: submit, SIGKILL, restart, verify (DESIGN.md S14).

The end-to-end crash-safety gate CI runs on every push, usable
locally as well:

    python -m repro.serve.smoke --workdir /tmp/serve_smoke

Two phases, each against a real ``python -m repro serve`` subprocess:

1. **crash safety** -- submit N mixed jobs (coalescible multispin
   specs + odd-shaped ones) through the HTTP client, SIGKILL the
   server as soon as the first batch starts, restart it with
   ``--drain-on-idle``, and assert: every acked job completes, each
   has EXACTLY one ``done`` record (the journal's ``job_table`` raises
   on duplicates), and every digest is bit-identical to a direct
   in-process ``Session`` run of the same spec (computed after the last
   server exits, so this process never holds a chip a server needs);

2. **coalescing** -- on a fresh directory, queue k compatible specs
   behind a blocker job and assert from the journal that all k ran as
   ONE batch and from ``metrics.json`` that the whole phase cost one
   compiled dispatch per batch (``chunk >= sweeps``).

SIGKILL -- not SIGTERM -- is the point: no handler runs, nothing
flushes, and the journal's fsync-before-ack contract is the only thing
standing between the farm and lost work.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import signal
import subprocess
import sys
import time

from repro.api import EngineSpec, LatticeSpec, RunSpec

from .journal import JOURNAL_NAME, Journal, job_table


def _specs(args):
    """N mixed submissions: ``args.k`` coalescible multispin jobs plus
    two odd ones (different engine / lattice), all counter-based so
    digests are chunk-grid-invariant."""
    out = []
    for i in range(args.k):
        out.append(RunSpec(
            lattice=LatticeSpec(n=args.n, m=args.n),
            engine=EngineSpec("multispin"),
            temperature=2.0 + 0.1 * i, seed=20 + i))
    out.append(RunSpec(lattice=LatticeSpec(n=2 * args.n, m=2 * args.n),
                       engine=EngineSpec("bitplane"),
                       temperature=2.3, seed=91))
    out.append(RunSpec(lattice=LatticeSpec(n=args.n, m=args.n),
                       engine=EngineSpec("basic_philox"),
                       temperature=1.8, seed=92))
    return out


def _reference_digests(specs, sweeps):
    from repro.api import Session
    refs = []
    for spec in specs:
        s = Session.open(spec)
        s.run(sweeps)
        refs.append(s.state_digest())
    return refs


def _server_cmd(args, workdir, drain_on_idle):
    cmd = [sys.executable, "-m", "repro", "serve", workdir,
           "--chunk", str(args.chunk),
           "--max-batch", str(args.max_batch),
           "--ckpt-every-sweeps", str(args.chunk),
           "--poll", "0.05"]
    if drain_on_idle:
        cmd.append("--drain-on-idle")
    return cmd


def _start_server(args, workdir, drain_on_idle=False):
    proc = subprocess.Popen(_server_cmd(args, workdir, drain_on_idle),
                            text=True, stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT)
    ep = os.path.join(workdir, "serve.json")
    deadline = time.monotonic() + args.timeout
    while time.monotonic() < deadline:
        if os.path.exists(ep):
            # the endpoint file must name THIS process (a restart
            # overwrites the previous server's file)
            with open(ep) as f:
                if json.load(f).get("pid") == proc.pid:
                    return proc
        if proc.poll() is not None:
            out, _ = proc.communicate()
            raise SystemExit(f"server died during startup "
                             f"(exit {proc.returncode}):\n{out}")
        time.sleep(0.05)
    proc.kill()
    raise SystemExit("server did not write serve.json in time")


def _journal_records(workdir):
    j = Journal(os.path.join(workdir, JOURNAL_NAME))
    try:
        return list(j.records)
    finally:
        j.close()


def _phase_crash(args) -> list:
    from .client import ServeClient
    workdir = os.path.join(args.workdir, "crash")
    shutil.rmtree(workdir, ignore_errors=True)
    os.makedirs(workdir, exist_ok=True)

    specs = _specs(args)
    print(f"# [1/2] crash drill: {len(specs)} jobs", flush=True)

    proc = _start_server(args, workdir)
    client = ServeClient(workdir)
    jids = [client.submit({"spec": s.to_dict(),
                           "sweeps": args.sweeps}) for s in specs]
    print(f"# submitted {jids}", flush=True)

    # SIGKILL as soon as the first batch starts: no handler, no flush
    deadline = time.monotonic() + args.timeout
    while time.monotonic() < deadline:
        if any(r.get("kind") == "start"
               for r in _journal_records(workdir)):
            break
        time.sleep(0.02)
    proc.send_signal(signal.SIGKILL)
    proc.wait(timeout=args.timeout)
    print(f"# SIGKILLed server pid {proc.pid}", flush=True)

    print("# restarting with --drain-on-idle", flush=True)
    proc = _start_server(args, workdir, drain_on_idle=True)
    out, _ = proc.communicate(timeout=args.timeout)
    print(out, end="", flush=True)
    if proc.returncode != 0:
        raise SystemExit(f"restarted server exited "
                         f"{proc.returncode}, want 0 (drained idle)")

    records = _journal_records(workdir)
    submits, dones = job_table(records)  # raises on duplicate done
    missing = [j for j in jids if j not in dones]
    if missing:
        raise SystemExit(f"jobs lost across the kill: {missing}")
    for jid in jids:
        done = dones[jid]
        if done["status"] != "completed":
            raise SystemExit(f"{jid} finished {done['status']}: "
                             f"{done.get('error')}")
    print(f"# crash drill: {len(jids)} jobs exactly-once", flush=True)
    return [(jid, spec, dones[jid]["digest"])
            for jid, spec in zip(jids, specs)]


def _check_digests(args, finished) -> None:
    """Every crash-drill digest against a direct in-process ``Session``
    run of the same spec.  Run after the last server has exited: this
    process touches JAX here and nowhere else, so on a chip it never
    holds the device while a server needs it."""
    print("# computing reference digests in-process", flush=True)
    refs = _reference_digests([spec for _, spec, _ in finished],
                              args.sweeps)
    for (jid, spec, got), want in zip(finished, refs):
        if got != want:
            raise SystemExit(
                f"{jid} ({spec.engine.name}): digest {got} != "
                f"direct-Session reference {want}")
    print(f"# crash drill OK: every digest bit-identical to a direct "
          f"run", flush=True)


def _phase_coalesce(args) -> None:
    from .client import ServeClient
    workdir = os.path.join(args.workdir, "coalesce")
    shutil.rmtree(workdir, ignore_errors=True)
    os.makedirs(workdir, exist_ok=True)
    print(f"# [2/2] coalescing drill: {args.k} compatible specs "
          f"behind a blocker", flush=True)

    # chunk >= sweeps: every batch is exactly one compiled dispatch
    co_args = argparse.Namespace(**{**vars(args),
                                    "chunk": args.sweeps})
    proc = _start_server(co_args, workdir, drain_on_idle=True)
    client = ServeClient(workdir)
    blocker = RunSpec(lattice=LatticeSpec(n=2 * args.n, m=2 * args.n),
                      engine=EngineSpec("multispin"),
                      temperature=2.5, seed=7)
    bid = client.submit({"spec": blocker.to_dict(),
                         "sweeps": args.sweeps})
    jids = [client.submit({"spec": s.to_dict(),
                           "sweeps": args.sweeps})
            for s in _specs(args)[:args.k]]
    out, _ = proc.communicate(timeout=args.timeout)
    print(out, end="", flush=True)
    if proc.returncode != 0:
        raise SystemExit(f"coalesce server exited {proc.returncode}")

    starts = [r for r in _journal_records(workdir)
              if r.get("kind") == "start"]
    fused = [s for s in starts if set(jids) <= set(s["jobs"])]
    if not fused:
        grouping = [s["jobs"] for s in starts]
        raise SystemExit(
            f"jobs {jids} did not coalesce into one batch; start "
            f"records grouped them as {grouping}")
    with open(os.path.join(workdir, "metrics.json")) as f:
        counters = json.load(f)["counters"]
    dispatches = counters.get("dispatches", 0)
    want = len(starts)  # one compiled dispatch per batch
    if dispatches != want:
        raise SystemExit(
            f"dispatches={dispatches}, want {want} (one per batch "
            f"at chunk >= sweeps); batches: "
            f"{[s['batch'] for s in starts]}")
    _ = bid
    print(f"# coalescing OK: {args.k} specs + 1 blocker ran as "
          f"{len(starts)} batches / {dispatches} compiled dispatches",
          flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.serve.smoke",
        description="sweep-farm crash + coalescing drill")
    ap.add_argument("--workdir", default="results/serve_smoke")
    ap.add_argument("--n", type=int, default=16,
                    help="coalescible-job lattice size")
    ap.add_argument("--k", type=int, default=4,
                    help="coalescible multispin jobs")
    ap.add_argument("--sweeps", type=int, default=192)
    ap.add_argument("--chunk", type=int, default=32)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--timeout", type=float, default=600.0,
                    help="per-wait wall-clock budget (s)")
    args = ap.parse_args(argv)
    finished = _phase_crash(args)
    _phase_coalesce(args)
    _check_digests(args, finished)
    print("serve smoke OK: crash safety + coalescing verified")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
