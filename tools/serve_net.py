"""Network serving launcher: N engine replicas behind the front door.

Two modes, one file:

- ``--replica``: run ONE engine + SSE frontend (serving/frontend.py)
  in THIS process on an ephemeral port, print ``{"port": N}`` as the
  first stdout line, and serve until stdin closes (the parent's exit
  hangs up the pipe — no orphan pollers). This is the unit the front
  door spawns, and the unit a real deployment would run per host.

- front-door mode (default): spawn ``--replicas N`` replica
  subprocesses (same model seed → identical weights, so completions
  are bitwise-independent of routing), put them behind the cache-aware
  router (serving/router.py), and either serve (``--serve``) or run
  the seeded network smoke (``--smoke``): replay a tools/traffic.py
  scenario through the door and print a serve_bench-compatible SLA row
  as the LAST stdout line — requests/token counters from the client's
  own ledger, router counters from the router, global prefix-hit
  tokens summed over the replicas' ``/vars`` scrapes. The smoke's
  sequential replay makes every one of those numbers a pure function
  of the seed (the bench_compare zero-drift contract; wall-clock
  throughput is deliberately NOT emitted on network rows).

The CI "Network serving drill" runs ``--smoke`` twice on
``shared_prefix`` (``--policy prefix`` vs ``--policy round_robin``) to
pin cache-aware routing's global prefix-hit win, and once more with
``--rolling-deploy-at K --concurrency 4`` to prove a mid-load rolling
deploy completes with zero failed and zero duplicated requests.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import threading
import time

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)


def add_engine_args(p: argparse.ArgumentParser) -> None:
    """The serve_bench-compatible subset of engine knobs a replica
    needs (tiny random-weight model: this drills the NETWORK plane —
    routing, streaming, deploys — not model quality)."""
    from distributed_training_tpu.config import kv_page_size_arg

    p.add_argument("--vocab-size", type=int, default=256)
    p.add_argument("--num-layers", type=int, default=2)
    p.add_argument("--num-heads", type=int, default=2)
    p.add_argument("--hidden-dim", type=int, default=64)
    p.add_argument("--model-max-len", type=int, default=256)
    p.add_argument("--max-batch", type=int, default=4)
    p.add_argument("--max-len", type=int, default=192)
    p.add_argument("--max-new-tokens", type=int, default=16)
    p.add_argument("--temperature", type=float, default=0.0)
    p.add_argument("--kv-page-size", type=kv_page_size_arg, default=8)
    p.add_argument("--kv-pages", type=int, default=256)
    p.add_argument("--no-prefix-cache", action="store_true",
                   default=False)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--journal-dir", type=str, default=None)
    p.add_argument("--trace-dir", type=str, default=None,
                   help="fleet tracing: every participant (door + each "
                        "replica incarnation) writes one Chrome trace "
                        "here, named by its REAL pid; merge with "
                        "tools/fleet_trace.py")


def build_engine(args: argparse.Namespace, trace=None):
    import jax
    import numpy as np

    from distributed_training_tpu.config import ServeConfig
    from distributed_training_tpu.models import get_model
    from distributed_training_tpu.serving import Engine

    model = get_model("transformer_lm", num_classes=args.vocab_size,
                      num_layers=args.num_layers,
                      num_heads=args.num_heads,
                      hidden_dim=args.hidden_dim,
                      max_len=args.model_max_len)
    params = model.init(jax.random.PRNGKey(args.seed),
                        np.zeros((1, 8), np.int32))["params"]
    cfg = ServeConfig(
        max_batch=args.max_batch, max_len=args.max_len,
        max_new_tokens=args.max_new_tokens,
        temperature=args.temperature,
        kv_page_size=args.kv_page_size, kv_pages=args.kv_pages,
        prefix_cache=not args.no_prefix_cache,
        journal_dir=args.journal_dir, seed=args.seed)
    return Engine(model, params, cfg, trace=trace)


def run_replica(args: argparse.Namespace) -> int:
    from distributed_training_tpu.observability.trace import fleet_session
    from distributed_training_tpu.runtime.backend import enable_compile_cache
    from distributed_training_tpu.serving.frontend import ServingFrontend

    enable_compile_cache()
    # Fleet tracing: the replica's session pid is os.getpid() and the
    # file carries the pid in its name, so a SIGKILLed incarnation's
    # trace survives its successor (tools/fleet_trace.py merges them
    # onto distinct Perfetto tracks). The component prefix "replica"
    # is what fleet_trace --check-failover keys on.
    trace, trace_path = fleet_session(f"replica-{args.name}",
                                      args.trace_dir)
    engine = build_engine(args, trace=trace)
    engine.recover()
    frontend = ServingFrontend(engine, port=args.port, trace=trace,
                               trace_path=trace_path).start()
    print(json.dumps({"replica": args.name, "port": frontend.port}),
          flush=True)
    stop = threading.Event()
    signal.signal(signal.SIGTERM, lambda *_: stop.set())
    try:
        # Park until the parent hangs up the pipe or SIGTERMs us.
        while not stop.is_set():
            if not sys.stdin.read(1):
                break
    except (KeyboardInterrupt, OSError):
        pass
    frontend.stop()
    if engine.journal is not None:
        engine.journal.shutdown()
    return 0


class ReplicaProc:
    """One spawned replica subprocess + its discovered port."""

    def __init__(self, index: int, args: argparse.Namespace):
        cmd = [sys.executable, "-m", "tools.serve_net", "--replica",
               "--name", f"r{index}", "--port", "0",
               "--vocab-size", str(args.vocab_size),
               "--num-layers", str(args.num_layers),
               "--num-heads", str(args.num_heads),
               "--hidden-dim", str(args.hidden_dim),
               "--model-max-len", str(args.model_max_len),
               "--max-batch", str(args.max_batch),
               "--max-len", str(args.max_len),
               "--max-new-tokens", str(args.max_new_tokens),
               "--temperature", str(args.temperature),
               "--kv-page-size", str(args.kv_page_size),
               "--kv-pages", str(args.kv_pages),
               "--seed", str(args.seed)]
        if args.no_prefix_cache:
            cmd.append("--no-prefix-cache")
        if args.journal_dir:
            cmd += ["--journal-dir",
                    os.path.join(args.journal_dir, f"r{index}")]
        if getattr(args, "trace_dir", None):
            cmd += ["--trace-dir", args.trace_dir]
        self.name = f"r{index}"
        self.proc = subprocess.Popen(
            cmd, cwd=REPO_ROOT, stdin=subprocess.PIPE,
            stdout=subprocess.PIPE, text=True)
        line = self.proc.stdout.readline()
        if not line:
            raise RuntimeError(
                f"replica {self.name} died before reporting its port "
                f"(exit {self.proc.poll()})")
        self.port = int(json.loads(line)["port"])
        self.url = f"http://127.0.0.1:{self.port}"

    def stop(self) -> None:
        if self.proc.poll() is None:
            try:
                self.proc.stdin.close()  # replica parks on stdin EOF
                self.proc.wait(timeout=10.0)
            except (OSError, subprocess.TimeoutExpired):
                self.proc.terminate()
                try:
                    self.proc.wait(timeout=5.0)
                except subprocess.TimeoutExpired:
                    self.proc.kill()


def _replica_stats(url: str) -> dict:
    """One replica's serving stats via its /vars scrape."""
    import urllib.request

    with urllib.request.urlopen(url + "/vars", timeout=10.0) as resp:
        return json.loads(resp.read())["serving"]


def _settle_and_audit(sup, timeout_s: float = 60.0):
    """Post-replay fleet audit: wait for each replica to drain to the
    idle steady state (a chaos-killed replica may still be mid-restart
    or finishing recovered zombie work), run its page-balance leak
    audit, and scrape its /vars. Returns (per_replica_stats,
    balance_violations); an unreachable or never-settling replica
    counts as a violation — a leak audit that cannot run must not
    pass silently. Re-reads ``sup.handles[i]`` every poll: a restart
    swaps the handle (new port) while we wait."""
    import urllib.request

    stats, violations = [], 0
    for i in range(len(sup.handles)):
        t0 = time.monotonic()
        audited = False
        while time.monotonic() - t0 < timeout_s:
            h = sup.handles[i]
            try:
                with urllib.request.urlopen(
                        urllib.request.Request(
                            h.url + "/probe", data=b"{}",
                            headers={"Content-Type":
                                     "application/json"}),
                        timeout=5.0) as resp:
                    probe = json.loads(resp.read())
                if probe.get("queue_depth", 1) or \
                        probe.get("active_slots", 1):
                    time.sleep(0.1)
                    continue
                with urllib.request.urlopen(
                        urllib.request.Request(
                            h.url + "/admin/check_balanced", data=b"{}",
                            headers={"Content-Type":
                                     "application/json"}),
                        timeout=10.0) as resp:
                    verdict = json.loads(resp.read())
                if not verdict.get("balanced", False):
                    print(f"[serve_net] BALANCE VIOLATION on {h.name}: "
                          f"{verdict.get('error')}", file=sys.stderr)
                    violations += 1
                stats.append(_replica_stats(h.url))
                audited = True
                break
            except Exception:
                time.sleep(0.25)  # mid-restart: keep polling
        if not audited:
            print(f"[serve_net] replica {sup.handles[i].name} never "
                  f"settled for the balance audit", file=sys.stderr)
            violations += 1
            stats.append({})
    return stats, violations


def run_front_door(args: argparse.Namespace) -> int:
    from distributed_training_tpu.observability.trace import fleet_session
    from distributed_training_tpu.runtime.backend import expected_platform

    # One process per chip: every replica is its own process asking JAX
    # for every device, and this launcher assigns none (ROADMAP B6 owns
    # chip assignment). On a TPU host only one replica can be placed —
    # refuse before spawning anything. The door itself stays off JAX's
    # backends (it imports the package, never a device).
    if args.replicas > 1 and expected_platform() == "tpu":
        print(f"serve_net: refusing --replicas {args.replicas} on platform "
              f"'tpu': each replica process would claim every chip and a "
              f"chip belongs to one process; run --replicas 1 here (or "
              f"JAX_PLATFORMS=cpu for the CPU drills)", file=sys.stderr)
        return 2
    from distributed_training_tpu.serving.router import (
        HttpReplica, Router, RouterFrontDoor)
    from distributed_training_tpu.serving.supervisor import (
        ReplicaSupervisor)
    from tools.traffic import make_scenario, replay_over_http

    # One trace session for the door process; the router (breaker-skip
    # instants) and the supervisor (death/restart instants) share it —
    # their lanes interleave with route/relay on the door's pid.
    trace, trace_path = fleet_session("door", args.trace_dir)

    # The supervisor owns the replica processes: spawn, death/wedge
    # detection, restart-with-journal. A restart rebinds the router's
    # HttpReplica at the replacement port (a plain string store — the
    # breaker keeps traffic off the replica until it proves out).
    router_box: list = []

    def _on_restart(i: int, handle) -> None:
        if router_box:
            router_box[0].replicas[i].url = handle.url.rstrip("/")
        print(f"[serve_net] supervisor restarted {handle.name} on "
              f"port {handle.port}", file=sys.stderr)

    sup = ReplicaSupervisor(
        lambda i: ReplicaProc(i, args), args.replicas,
        wedge_timeout_s=args.wedge_timeout_s or None,
        on_restart=_on_restart, trace=trace).start()
    replicas = sup.handles
    router = Router([HttpReplica(r.url, name=r.name) for r in replicas],
                    policy=args.policy,
                    breaker_threshold=args.breaker_threshold,
                    breaker_cooldown_s=args.breaker_cooldown_s)
    router_box.append(router)

    # Chaos: SIGKILL the replica serving request N after its first
    # relayed token — mid-stream by construction, through the
    # supervisor's handle so detection/restart run the real path.
    kill_state = {"killed": False}

    def _chaos_hook(seq: int, delivered: int, replica_idx) -> None:
        if (args.kill_replica_at_request > 0 and not kill_state["killed"]
                and seq == args.kill_replica_at_request
                and delivered >= 1 and replica_idx is not None):
            kill_state["killed"] = True
            print(f"[serve_net] chaos: SIGKILL replica {replica_idx} "
                  f"mid-stream (request {seq}, {delivered} tokens "
                  f"delivered)", file=sys.stderr)
            sup.kill(replica_idx)

    door = RouterFrontDoor(
        router, port=args.port,
        chaos_hook=(_chaos_hook if args.kill_replica_at_request > 0
                    else None),
        trace=trace, trace_path=trace_path,
        supervisor_snapshot=sup.supervisor_snapshot).start()
    print(json.dumps({"port": door.port, "policy": args.policy,
                      "replicas": [{"name": r.name, "port": r.port}
                                   for r in replicas]}), flush=True)
    try:
        if not args.smoke:
            print(f"[serve_net] front door on {door.url('')} "
                  f"({args.replicas} replica(s), policy={args.policy}); "
                  f"Ctrl-C to stop", file=sys.stderr)
            try:
                while True:
                    time.sleep(1.0)
            except KeyboardInterrupt:
                return 0
            finally:
                pass

        reqs = make_scenario(
            args.scenario, seed=args.seed, requests=args.requests,
            rate=args.rate, mean_prompt_len=args.mean_prompt_len,
            max_prompt_len=args.max_prompt_len,
            max_new_tokens=args.max_new_tokens,
            vocab_size=args.vocab_size,
            budget=args.max_len)
        deploy_thread = None
        if args.rolling_deploy_at > 0:
            # Chaos drill: fire the rolling deploy while the replay is
            # mid-load (after a short head-start so every replica has
            # accepted work), from a side thread — requests keep
            # flowing through the rotation the whole time.
            def _deploy() -> None:
                time.sleep(args.rolling_deploy_delay_s)
                router.rolling_deploy()

            deploy_thread = threading.Thread(
                target=_deploy, name="chaos-deploy", daemon=True)
            deploy_thread.start()
        # Chaos: the disconnect drill hangs up request M's client
        # socket after K streamed tokens — the replica must notice the
        # dead pipe, cancel the in-flight request, and free its pages.
        drop_at = None
        if args.drop_client_at_token > 0:
            drop_at = {args.drop_client_at_request - 1:
                       args.drop_client_at_token}
        t0 = time.monotonic()
        results = replay_over_http(
            door.url("/generate"), reqs, stream=not args.unary,
            concurrency=args.concurrency, timeout_s=args.timeout_s,
            drop_at=drop_at)
        wall_s = time.monotonic() - t0
        if deploy_thread is not None:
            deploy_thread.join(timeout=120.0)

        dropped = set(drop_at or ())
        done = [r for r in results if r is not None]
        mismatched = sum(1 for r in done
                         if r.get("streamed_tokens") is not None
                         and r["streamed_tokens"] != r["tokens"])
        if args.completions_out:
            with open(args.completions_out, "w") as fh:
                json.dump([{"index": i, "uid": int(r["uid"]),
                            "reason": r["finish_reason"],
                            "tokens": [int(t) for t in r["tokens"]]}
                           for i, r in enumerate(results)
                           if r is not None], fh)
            print(f"[serve_net] completions: {args.completions_out} "
                  f"({len(done)} requests)", file=sys.stderr)

        # Post-replay fleet audit FIRST: it waits out an in-flight
        # restart (the supervisor's spawn blocks through journal
        # recovery) and a cancel landing a step after the client
        # vanished — the supervisor/router snapshots after it are the
        # settled fault counters the drill pins bitwise.
        chaos = bool(drop_at) or kill_state["killed"]
        per_replica, balance_violations = _settle_and_audit(
            sup, timeout_s=120.0 if chaos else 20.0)
        snap = router.router_snapshot()
        sup_snap = sup.supervisor_snapshot()
        fleet = door.fleet_snapshot()
        from tools.traffic import trace_roundtrip_mismatches
        trace_bad = trace_roundtrip_mismatches(results)
        if args.fleet_out:
            # Self-scrape the federated plane AFTER the replay settled
            # — the artifact CI asserts family presence and staleness
            # markers on without re-standing the fleet up.
            import urllib.request
            fleet_doc = {}
            for key, path in (("metrics_text", "/fleet/metrics"),
                              ("vars", "/fleet/vars"),
                              ("replicas", "/fleet/replicas")):
                with urllib.request.urlopen(door.url(path),
                                            timeout=30.0) as resp:
                    body = resp.read().decode("utf-8", "replace")
                fleet_doc[key] = (body if key == "metrics_text"
                                  else json.loads(body))
            with open(args.fleet_out, "w") as fh:
                json.dump(fleet_doc, fh)
            print(f"[serve_net] fleet scrape: {args.fleet_out}",
                  file=sys.stderr)
        row = {
            "scenario": args.scenario,
            "requests": len(reqs),
            "requests_finished": len(done),
            # A chaos-dropped client is an injected fault, not a
            # serving failure — excluded from the failure gate.
            "requests_failed": sum(
                1 for i, r in enumerate(results)
                if r is None and i not in dropped),
            "tokens_emitted": sum(len(r["tokens"]) for r in done),
            "stream_vs_done_mismatches": mismatched,
            "replicas": args.replicas,
            "concurrency": args.concurrency,
            "router_requests_routed": snap["router_requests_routed"],
            "router_prefix_routed": snap["router_prefix_routed"],
            "router_fallback_routed": snap["router_fallback_routed"],
            "router_retries": snap["router_retries"],
            "router_deploys_completed": snap["router_deploys_completed"],
            "router_deploy_errors": snap["router_deploy_errors"],
            # Fleet fault tolerance (zero on every no-fault row — the
            # bench_compare zero-drift contract; a chaos drill pins
            # them bitwise across independent kill cycles instead).
            "replica_restarts": sup_snap["replica_restarts"],
            "breaker_opens": snap["router_breaker_opens"],
            "failover_resumes": snap["router_failover_resumes"],
            # Fleet ledger (zero-tolerance conservation gate): every
            # completed proxied request audited cross-hop; the joined/
            # absent split separates live replica ledgers from
            # journal-redelivered results whose wall detail died with
            # the old process. Trace round-trip: the id on the done
            # payload must equal the response-header echo.
            "fleet_ledger_requests": fleet["fleet_ledger_requests"],
            "fleet_ledger_conservation_violations":
                fleet["fleet_ledger_conservation_violations"],
            "fleet_replica_ledger_joined":
                fleet["fleet_replica_ledger_joined"],
            "fleet_replica_ledger_absent":
                fleet["fleet_replica_ledger_absent"],
            "trace_roundtrip_mismatches": trace_bad,
            "requests_cancelled": sum(
                int(s.get("requests_cancelled", 0))
                for s in per_replica),
            "balance_violations": balance_violations,
            # Global cache economics: prefill compute saved ACROSS the
            # fleet — the number cache-aware routing exists to raise.
            "prefix_cache_hit_tokens": sum(
                int(s.get("prefix_cache_hit_tokens", 0))
                for s in per_replica),
            "prefix_cache_hit_requests": sum(
                int(s.get("prefix_cache_hit_requests", 0))
                for s in per_replica),
            # Wall time rides as context only (never gated: network
            # smoke wall-clock on shared CI is not a metric).
            "wall_s": round(wall_s, 3),
        }
        print(json.dumps(row, allow_nan=False))
        if fleet["fleet_ledger_conservation_violations"]:
            print(f"[serve_net] FLEET LEDGER VIOLATION: "
                  f"{fleet['fleet_ledger_violation_last']}",
                  file=sys.stderr)
        return 0 if (not row["requests_failed"] and not mismatched
                     and not row["router_deploy_errors"]
                     and not balance_violations
                     and not row["fleet_ledger_conservation_violations"]
                     and not trace_bad) else 1
    finally:
        door.stop()
        sup.stop()
        if trace is not None and trace_path:
            trace.save(trace_path)
            print(f"[serve_net] trace: {trace_path} "
                  f"({len(trace)} events)", file=sys.stderr)


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(
        prog="python -m tools.serve_net",
        description="network serving: replicas + cache-aware front door")
    p.add_argument("--replica", action="store_true", default=False,
                   help="internal: run ONE replica (engine + frontend) "
                        "in this process")
    p.add_argument("--name", type=str, default="r0")
    p.add_argument("--port", type=int, default=0)
    p.add_argument("--replicas", type=int, default=2)
    p.add_argument("--policy", type=str, default="prefix",
                   choices=["prefix", "round_robin"])
    p.add_argument("--serve", action="store_true", default=False,
                   help="front-door mode: serve until interrupted "
                        "(default when --smoke is not given)")
    p.add_argument("--smoke", action="store_true", default=False,
                   help="replay a seeded scenario through the door and "
                        "print a serve_bench-compatible SLA row")
    # Smoke / client knobs (mirror tools/traffic.py client mode).
    p.add_argument("--scenario", type=str, default="shared_prefix")
    p.add_argument("--requests", type=int, default=24)
    p.add_argument("--rate", type=float, default=16.0)
    p.add_argument("--mean-prompt-len", type=int, default=32)
    p.add_argument("--max-prompt-len", type=int, default=64)
    p.add_argument("--concurrency", type=int, default=1)
    p.add_argument("--unary", action="store_true", default=False)
    p.add_argument("--timeout-s", type=float, default=180.0)
    p.add_argument("--completions-out", type=str, default=None)
    p.add_argument("--fleet-out", type=str, default=None,
                   help="after the replay settles, self-scrape "
                        "/fleet/metrics + /fleet/vars + /fleet/replicas "
                        "from the door into this JSON file (the CI "
                        "fleet-drill artifact)")
    p.add_argument("--rolling-deploy-at", type=int, default=0,
                   help="chaos drill: >0 starts a rolling deploy from a "
                        "side thread while the replay is in flight")
    p.add_argument("--rolling-deploy-delay-s", type=float, default=0.5)
    p.add_argument("--kill-replica-at-request", type=int, default=0,
                   help="chaos drill: SIGKILL the replica serving the "
                        "N-th routed request (1-based) after its first "
                        "streamed token — the supervisor restarts it, "
                        "the router fails the stream over mid-SSE")
    p.add_argument("--drop-client-at-token", type=int, default=0,
                   help="chaos drill: >0 hangs up one client socket "
                        "after K streamed tokens — the replica must "
                        "cancel the request and free its pages")
    p.add_argument("--drop-client-at-request", type=int, default=1,
                   help="which request (1-based) the drop-client drill "
                        "hangs up")
    p.add_argument("--breaker-threshold", type=int, default=3,
                   help="consecutive failures before a replica's "
                        "circuit breaker opens (chaos drills pass 1 "
                        "for deterministic fault counters)")
    p.add_argument("--breaker-cooldown-s", type=float, default=5.0,
                   help="seconds an open breaker cools before its "
                        "half-open trial probe")
    p.add_argument("--wedge-timeout-s", type=float, default=0.0,
                   help=">0 arms the supervisor's wedged-replica "
                        "detector at this heartbeat-freeze timeout")
    add_engine_args(p)
    args = p.parse_args(argv)
    if args.replica:
        return run_replica(args)
    return run_front_door(args)


if __name__ == "__main__":
    sys.exit(main())
