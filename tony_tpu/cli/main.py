"""The ``tony`` command-line front end.

Analog of the reference's ``tony-cli`` module (``ClusterSubmitter`` /
``NotebookSubmitter`` — SURVEY.md §2.3): subcommands wrap the client and
auxiliary services.

    tony submit --conf_file job.xml --executes "python train.py"
    tony history [--root DIR]
    tony portal [--port N]
"""

from __future__ import annotations

import sys

from tony_tpu import constants


def _cmd_submit(argv: list[str]) -> int:
    from tony_tpu.cluster.client import main as client_main

    return client_main(argv)


def _cmd_history(argv: list[str]) -> int:
    from tony_tpu.cli.history import main as history_main

    return history_main(argv)


def _cmd_history_server(argv: list[str]) -> int:
    from tony_tpu.histserver.server import main as server_main

    return server_main(argv)


def _cmd_bench(argv: list[str]) -> int:
    from tony_tpu.cli.history import main_bench

    return main_bench(argv)


def _cmd_portal(argv: list[str]) -> int:
    from tony_tpu.portal.server import main as portal_main

    return portal_main(argv)


def _cmd_notebook(argv: list[str]) -> int:
    from tony_tpu.cli.notebook import main as notebook_main

    return notebook_main(argv)


def _cmd_data_prep(argv: list[str]) -> int:
    from tony_tpu.data.prepare import main as prep_main

    return prep_main(argv)


def _cmd_serve(argv: list[str]) -> int:
    from tony_tpu.cli.serve import main as serve_main

    return serve_main(argv)


def _cmd_lint(argv: list[str]) -> int:
    from tony_tpu.cli.lint import main as lint_main

    return lint_main(argv)


def _cmd_chaos(argv: list[str]) -> int:
    from tony_tpu.cli.chaos import main as chaos_main

    return chaos_main(argv)


def _cmd_trace(argv: list[str]) -> int:
    from tony_tpu.cli.trace import main as trace_main

    return trace_main(argv)


def _cmd_profile(argv: list[str]) -> int:
    from tony_tpu.cli.introspect import main_profile

    return main_profile(argv)


def _cmd_logs(argv: list[str]) -> int:
    from tony_tpu.cli.introspect import main_logs

    return main_logs(argv)


def _cmd_top(argv: list[str]) -> int:
    from tony_tpu.cli.introspect import main_top

    return main_top(argv)


def _cmd_resize(argv: list[str]) -> int:
    from tony_tpu.cli.elastic import main_resize

    return main_resize(argv)


def _cmd_goodput(argv: list[str]) -> int:
    from tony_tpu.cli.goodput import main as goodput_main

    return goodput_main(argv)


def _cmd_slo(argv: list[str]) -> int:
    from tony_tpu.cli.slo import main as slo_main

    return slo_main(argv)


def _cmd_sim(argv: list[str]) -> int:
    from tony_tpu.cli.sim import main as sim_main

    return sim_main(argv)


def _cmd_explain(argv: list[str]) -> int:
    from tony_tpu.cli.explain import main as explain_main

    return explain_main(argv)


def _cmd_loadtest(argv: list[str]) -> int:
    from tony_tpu.cli.loadtest import main as loadtest_main

    return loadtest_main(argv)


def _cmd_cbench(argv: list[str]) -> int:
    from tony_tpu.cli.cbench import main as cbench_main

    return cbench_main(argv)


def _cmd_mini(argv: list[str]) -> int:
    """Self-contained sandbox: submit a smoke gang against the local resource
    manager and print the verdict + history location.

    Analog of the reference's ``tony-mini`` single-node sandbox (SURVEY.md
    §2.3) — one command to see the whole submit→AM→executor→verdict spine
    work on this machine, no configuration needed.
    """
    import argparse
    import os
    import sys as _sys
    import tempfile

    from tony_tpu.cluster.client import Client
    from tony_tpu.config import TonyConfig, keys

    p = argparse.ArgumentParser(prog="tony mini", description=_cmd_mini.__doc__)
    p.add_argument("--workers", type=int, default=2)
    p.add_argument(
        "--distributed", action="store_true",
        help="workers form a jax.distributed group and run a cross-process "
             "collective (CPU backend) instead of the env-echo smoke",
    )
    p.add_argument("--root", default=None, help="sandbox dir (default: a temp dir)")
    args = p.parse_args(argv)

    root = args.root or tempfile.mkdtemp(prefix="tony-mini-")
    if args.distributed:
        # -m so this works from an installed wheel, not just a source checkout
        command = f"{_sys.executable} -m tony_tpu.cli.distributed_smoke"
    else:
        command = (
            f"{_sys.executable} -c \"import os; "
            f"print('hello from', os.environ['JOB_NAME'], os.environ['TASK_INDEX'], "
            f"'of', os.environ['TASK_NUM'])\""
        )
    cfg = TonyConfig({
        keys.STAGING_ROOT: root,
        keys.EXECUTES: command,
        keys.APPLICATION_FRAMEWORK: "jax",
        keys.jobtype_key("worker", keys.INSTANCES_SUFFIX): str(args.workers),
    })
    client = Client(cfg)
    handle = client.submit()
    final = client.monitor_application(handle)
    print(f"[tony-mini] sandbox root: {root}")
    print(f"[tony-mini] task logs:    {os.path.join(root, handle.app_id, 'logs')}")
    print(f"[tony-mini] history:      tony history --root {os.path.join(root, 'history')}")
    return 0 if final.name == "SUCCEEDED" else 1


def _cmd_pool(argv: list[str]) -> int:
    """Stand up a multi-host pool on this machine: the pool service (RM
    analog) plus one NodeAgent process per emulated host, then print the
    ``rm:host:port`` spec to submit against. On a real cluster you run
    ``python -m tony_tpu.cluster.pool`` on the coordinator and
    ``python -m tony_tpu.cluster.agent`` on every host instead — this
    command is those daemons wired together on loopback.
    """
    import argparse
    import os
    import secrets
    import signal as _signal
    import subprocess
    import sys as _sys
    import threading

    from tony_tpu.cluster.pool import PoolService
    from tony_tpu.cluster.resources import DEFAULT_CHIPS_PER_HOST, SliceSpec

    p = argparse.ArgumentParser(prog="tony pool", description=_cmd_pool.__doc__)
    p.add_argument("--spec", default="",
                   help="TPU pool, e.g. 'v5e-8x2' (slice spec x num slices); empty → CPU-only hosts")
    p.add_argument("--hosts", type=int, default=2, help="host agents when no --spec (CPU pool)")
    p.add_argument("--port", type=int, default=0)
    p.add_argument("--memory", default="64g", help="memory per host")
    p.add_argument("--vcores", type=int, default=64)
    p.add_argument("--queues", default="default=1.0",
                   help="capacity queues 'name=share,...' (tony.pool.queues)")
    p.add_argument("--preemption", action="store_true",
                   help="let waiting higher-priority jobs evict lower-priority ones, "
                        "and under-share queues reclaim capacity from over-share borrowers")
    p.add_argument("--preemption-grace-ms", type=int, default=0,
                   help="wait this long before cross-queue reclaim evicts borrowers "
                        "(tony.pool.preemption.grace-ms)")
    p.add_argument("--preemption-drain-ms", type=int, default=0,
                   help="cooperative drain window before eviction kills fire — the "
                        "victim checkpoints and yields inside it "
                        "(tony.pool.preemption.drain-ms; 0 = immediate kill)")
    p.add_argument("--preemption-min-runtime-ms", type=int, default=0,
                   help="a just-admitted app is not evictable for this long "
                        "(tony.pool.preemption.min-runtime-ms)")
    p.add_argument("--preemption-budget", type=int, default=0,
                   help="max evictions/shrinks a queue may cause per window "
                        "(tony.pool.preemption.budget; 0 = unlimited)")
    p.add_argument("--journal-file", default="",
                   help="recovery journal (tony.pool.journal.file): a restarted "
                        "pool replays it and re-adopts live work instead of "
                        "forgetting every admitted app")
    p.add_argument("--scheduler", default=None, choices=("indexed", "reference"),
                   help="scheduler pass implementation (tony.pool.scheduler.indexed): "
                        "'indexed' evaluates over incrementally-maintained indices, "
                        "'reference' is the full-rescan oracle — identical decisions "
                        "either way (tony sim --parity proves it). Default: the "
                        "config key (site file honored), i.e. indexed")
    args = p.parse_args(argv)

    from tony_tpu.cluster.pool import parse_queue_spec

    scheduler_indexed = args.scheduler != "reference"
    if not args.journal_file or args.scheduler is None:
        # honor the documented config keys like pool.main does: the dev
        # helper must not silently disable journaling — or un-flip the
        # scheduler kill switch — an operator configured in the site file
        site = os.path.join(os.getcwd(), constants.TONY_SITE_CONF)
        if os.path.exists(site):
            from tony_tpu.config import TonyConfig, keys as _keys

            site_conf = TonyConfig.from_layers(site_file=site)
            if not args.journal_file:
                args.journal_file = site_conf.get(_keys.POOL_JOURNAL_FILE) or ""
            if args.scheduler is None:
                scheduler_indexed = site_conf.get_bool(
                    _keys.POOL_SCHEDULER_INDEXED, True)
    secret = os.environ.get(constants.ENV_POOL_SECRET) or secrets.token_hex(16)
    svc = PoolService(port=args.port, secret=secret,
                      queues=parse_queue_spec(args.queues),
                      preemption=args.preemption,
                      preemption_grace_ms=args.preemption_grace_ms,
                      preemption_drain_ms=args.preemption_drain_ms,
                      preemption_min_runtime_ms=args.preemption_min_runtime_ms,
                      preemption_budget=args.preemption_budget,
                      journal_path=args.journal_file or None,
                      scheduler_indexed=scheduler_indexed)
    svc.start()
    host, port = svc.address

    # the secret travels via env, never argv: /proc/<pid>/cmdline is
    # world-readable, agent.py's --secret already defaults to this env var
    agent_env = {**os.environ, constants.ENV_POOL_SECRET: secret}

    def agent_args(name: str, extra: list[str]) -> list[str]:
        return [
            _sys.executable, "-u", "-m", "tony_tpu.cluster.agent",
            "--rm", f"{host}:{port}", "--name", name,
            "--memory", args.memory, "--vcores", str(args.vcores), *extra,
        ]

    agents: list[subprocess.Popen] = []
    if args.spec:
        base, _, count = args.spec.rpartition("x")
        num_slices = int(count) if count.isdigit() and base else 1
        slice_spec = SliceSpec.parse(base if count.isdigit() and base else args.spec)
        rows, cols = slice_spec.topology
        per_host = min(DEFAULT_CHIPS_PER_HOST, slice_spec.chips)
        # ceil: a slice whose chip count is not a host multiple still
        # registers ALL its chips (the last host owns the remainder)
        hosts_per_slice = -(-slice_spec.chips // per_host)
        for s in range(num_slices):
            # tile the slice grid onto hosts row-major, per_host chips each
            linear = [(r, c) for r in range(rows) for c in range(cols)]
            for h in range(hosts_per_slice):
                chips = ";".join(f"{r},{c}" for r, c in linear[h * per_host:(h + 1) * per_host])
                agents.append(subprocess.Popen(agent_args(
                    f"slice{s}-host{h}",
                    ["--slice-id", str(s), "--slice", slice_spec.name, "--chips", chips],
                ), env=agent_env))
    else:
        for h in range(args.hosts):
            agents.append(subprocess.Popen(agent_args(f"host{h}", []), env=agent_env))

    print(f"[tony-pool] pool service on {host}:{port} with {len(agents)} host agents")
    print(f"[tony-pool] submit with: --conf tony.tpu.pool=rm:{host}:{port} "
          f"(pool secret in ${constants.ENV_POOL_SECRET}; pass it via env or "
          "--conf tony.tpu.pool.secret=...)")
    done = threading.Event()
    _signal.signal(_signal.SIGTERM, lambda *_: done.set())
    _signal.signal(_signal.SIGINT, lambda *_: done.set())
    done.wait()
    for a in agents:
        a.terminate()
    for a in agents:
        try:
            a.wait(timeout=5)
        except subprocess.TimeoutExpired:
            a.kill()
    svc.stop()
    return 0


_COMMANDS = {
    "submit": _cmd_submit,
    "pool": _cmd_pool,
    "history": _cmd_history,
    "history-server": _cmd_history_server,
    "bench": _cmd_bench,
    "portal": _cmd_portal,
    "notebook": _cmd_notebook,
    "serve": _cmd_serve,
    "mini": _cmd_mini,
    "data-prep": _cmd_data_prep,
    "lint": _cmd_lint,
    "chaos": _cmd_chaos,
    "trace": _cmd_trace,
    "profile": _cmd_profile,
    "logs": _cmd_logs,
    "top": _cmd_top,
    "resize": _cmd_resize,
    "goodput": _cmd_goodput,
    "slo": _cmd_slo,
    "sim": _cmd_sim,
    "explain": _cmd_explain,
    "loadtest": _cmd_loadtest,
    "cbench": _cmd_cbench,
}


def main(argv: list[str] | None = None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if not argv or argv[0] in ("-h", "--help"):
        print("usage: tony {submit|pool|history|history-server|bench|cbench|portal|notebook|serve|loadtest|mini|data-prep|lint|chaos|trace|profile|logs|top|resize|goodput|slo|sim|explain} [options]\n")
        print("  submit     submit and monitor a job (tony submit --help)")
        print("  pool       run a pool service + host agents on this machine (RM/NM analog)")
        print("  history    query the persistent history tier (list|show|compare|ingest|gc)")
        print("  history-server  run the history daemon: ingest finalized jobs, serve the query API")
        print("  bench      perf-regression gate over the checked-in BENCH_* trajectory (--gate)")
        print("  cbench     control-plane microbenchmarks at thousand-node scale (CBENCH records)")
        print("  portal     serve the history web portal")
        print("  notebook   launch an interactive notebook container + local proxy")
        print("  serve      run a replicated inference fleet (router + health + autoscaler) as an AM-supervised job")
        print("  loadtest   open-loop multi-session load harness against a serving endpoint (SERVE_BENCH records)")
        print("  mini       one-command local sandbox (smoke gang, optional --distributed)")
        print("  data-prep  tokenize text files into TONYTOK training shards")
        print("  lint       run the AST static-analysis suite (config/jit/lock/mesh discipline)")
        print("  chaos      run a job under a seeded fault schedule and assert recovery invariants")
        print("  trace      merge a traced job's spans into a Chrome/Perfetto timeline + summary")
        print("  profile    capture a jax.profiler trace on a RUNNING job's workers (no resubmit)")
        print("  logs       merge/tail a job's per-process structured logs in timestamp order")
        print("  top        refreshing live status view (per-task state, step rate, heartbeat age)")
        print("  resize     retarget a RUNNING job's per-type instance count (elastic rebuild)")
        print("  goodput    exact goodput/badput phase accounting + straggler skew + alert history")
        print("  slo        SLO error budgets + burn rates (status) and the history-backed verdict")
        print("  sim        replay seeded synthetic arrivals against the live scheduler policy (invariant check),")
        print("             or recorded history with --from-history (fidelity gate + what-if counterfactuals)")
        print("  explain    render the pool scheduler's decision provenance for an app or queue (flight recorder)")
        return 0
    cmd = _COMMANDS.get(argv[0])
    if cmd is None:
        print(f"tony: unknown command {argv[0]!r} (expected one of {sorted(_COMMANDS)})", file=sys.stderr)
        return 2
    return cmd(argv[1:])


if __name__ == "__main__":
    sys.exit(main())
