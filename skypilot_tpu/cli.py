"""CLI: the `stpu` command.

Reference analog: sky/cli.py (click groups for launch/exec/status/stop/
down/autostop/queue/logs/cancel/check/show-gpus + jobs/serve subcommands,
sky/cli.py:928,3337,3418). Every command parses args then calls the SDK —
no business logic lives here.
"""
from __future__ import annotations

import sys
from typing import List, Optional, Tuple

import click

from skypilot_tpu import exceptions


def _parse_env(env: Tuple[str, ...]) -> dict:
    out = {}
    for item in env:
        if "=" not in item:
            raise click.UsageError(f"--env {item!r} must be KEY=VALUE")
        k, v = item.split("=", 1)
        out[k] = v
    return out


def _load_task(entrypoint: str, env: Tuple[str, ...], overrides: dict):
    from skypilot_tpu.task import Task
    try:
        task = Task.from_yaml(entrypoint, env_overrides=_parse_env(env))
    except exceptions.SkyTpuError as e:
        raise click.ClickException(str(e)) from e
    for key, value in overrides.items():
        if value is None:
            continue
        if key == "num_nodes":
            task.num_nodes = value
        else:
            # Apply to every candidate so any_of fallbacks survive.
            task.set_resources(tuple(
                r.copy(**{key: value}) for r in task.resources))
    return task


@click.group()
@click.version_option(message="%(version)s")
def cli():
    """stpu: launch, manage, and serve AI workloads on TPU slices."""


def _confirm_launch_plan(task, cluster_name) -> None:
    """Print the optimized plan and ask before provisioning a NEW
    cluster. Pins task.best_resources so execution.launch does not
    re-optimize (the table prints once)."""
    from skypilot_tpu import global_user_state
    from skypilot_tpu import optimizer as optimizer_lib
    from skypilot_tpu.backends import slice_backend
    from skypilot_tpu.status_lib import ClusterStatus
    from skypilot_tpu.utils import dag_utils

    name = cluster_name or slice_backend.default_cluster_name()
    record = global_user_state.get_cluster_from_name(name)
    if record is not None and record["status"] == ClusterStatus.UP:
        click.echo(f"Running on existing cluster {name!r}.")
        return
    if record is not None and record["handle"] is not None:
        # STOPPED cluster: provisioning RESTARTS it with its stored
        # resources — re-optimizing here would show (and pin) a plan
        # the backend will ignore. Confirm what will actually run.
        res = getattr(record["handle"], "launched_resources", None)
        click.echo(f"Cluster {name!r} is stopped; restarting with its "
                   f"existing resources: {res!r}.")
        click.confirm(f"Restart cluster {name!r}. Proceed?",
                      default=True, abort=True)
        return
    dag = dag_utils.convert_entrypoint_to_dag(task)
    try:
        optimizer_lib.Optimizer.optimize(dag)  # prints the plan table
    except exceptions.SkyTpuError as e:
        raise click.ClickException(str(e)) from e
    click.confirm(f"Launching a new cluster {name!r}. Proceed?",
                  default=True, abort=True)


@cli.command()
@click.argument("entrypoint", required=True)
@click.option("--cluster", "-c", default=None, help="Cluster name.")
@click.option("--env", multiple=True, help="KEY=VALUE env overrides.")
@click.option("--num-nodes", type=int, default=None,
              help="Override number of slices.")
@click.option("--accelerator", "--gpus", "-t", default=None,
              help="Override slice type, e.g. tpu-v5e-16.")
@click.option("--use-spot/--no-use-spot", default=None)
@click.option("--zone", default=None)
@click.option("--region", default=None)
@click.option("--cloud", default=None)
@click.option("--dryrun", is_flag=True)
@click.option("--down", is_flag=True,
              help="Tear down the cluster when the job finishes.")
@click.option("--detach-run", "-d", is_flag=True)
@click.option("--idle-minutes-to-autostop", "-i", type=int, default=None)
@click.option("--retry-until-up", is_flag=True)
@click.option("--no-setup", is_flag=True)
@click.option("--yes", "-y", is_flag=True,
              help="Skip the launch confirmation prompt.")
def launch(entrypoint, cluster, env, num_nodes, accelerator, use_spot,
           zone, region, cloud, dryrun, down, detach_run,
           idle_minutes_to_autostop, retry_until_up, no_setup, yes):
    """Launch a task YAML on a (new or existing) slice cluster."""
    from skypilot_tpu import execution
    task = _load_task(entrypoint, env, {
        "num_nodes": num_nodes, "accelerator": accelerator,
        "use_spot": use_spot, "zone": zone, "region": region,
        "cloud": cloud,
    })
    # Plan + confirm before spending money (reference:
    # sky/cli.py:562-592 click.confirm after the optimizer table).
    # --yes and --dryrun skip it; reusing an already-UP cluster is not a
    # new spend, so it proceeds without asking too.
    if not yes and not dryrun:
        _confirm_launch_plan(task, cluster)
    try:
        job_id, handle = execution.launch(
            task, cluster_name=cluster, dryrun=dryrun, down=down,
            detach_run=detach_run,
            idle_minutes_to_autostop=idle_minutes_to_autostop,
            retry_until_up=retry_until_up, no_setup=no_setup)
    except exceptions.SkyTpuError as e:
        raise click.ClickException(str(e)) from e
    if job_id is not None:
        click.echo(f"Job submitted: {job_id} "
                   f"(cluster {handle.cluster_name})")


@cli.command(name="exec")
@click.argument("cluster", required=True)
@click.argument("entrypoint", required=True)
@click.option("--env", multiple=True)
@click.option("--detach-run", "-d", is_flag=True)
def exec_cmd(cluster, entrypoint, env, detach_run):
    """Run a task on an existing cluster (skip provision/setup)."""
    from skypilot_tpu import execution
    task = _load_task(entrypoint, env, {})
    try:
        job_id, _ = execution.exec(task, cluster, detach_run=detach_run)
    except exceptions.SkyTpuError as e:
        raise click.ClickException(str(e)) from e
    click.echo(f"Job submitted: {job_id} (cluster {cluster})")


def _human_ago(ts) -> str:
    """Unix seconds -> '42s ago' / '3h ago' / '2d ago'."""
    import time as time_lib
    if not ts:
        return "-"
    delta = max(0, int(time_lib.time() - ts))
    for unit, secs in (("d", 86400), ("h", 3600), ("m", 60)):
        if delta >= secs:
            return f"{delta // secs}{unit} ago"
    return f"{delta}s ago"


def _head_ip(handle) -> str:
    info = getattr(handle, "cluster_info", None)
    if info is None:
        return "-"
    try:
        head = info.get_head_instance()
    except Exception:  # noqa: BLE001 — partial/stale handle
        return "-"
    if head is None:
        return "-"
    return head.external_ip or head.internal_ip or "-"


def _price_per_hr(handle) -> str:
    res = getattr(handle, "launched_resources", None)
    if res is None:
        return "-"
    try:
        nodes = getattr(handle, "num_slices", 1) or 1
        return f"{res.hourly_price() * nodes:.2f}"
    except exceptions.SkyTpuError:
        return "-"  # accelerator missing from the catalog


def _print_events(events, header: bool = True) -> None:
    """Render lifecycle-event records as one aligned line each."""
    import time as time_lib
    if header:
        click.echo("{:<20} {:<12} {:<24} {:<18} {}".format(
            "WHEN", "KIND", "NAME", "EVENT", "DETAIL"))
    for rec in events:
        stamp = time_lib.strftime("%Y-%m-%d %H:%M:%S",
                                  time_lib.localtime(rec.get("ts", 0)))
        detail = " ".join(
            f"{k}={v}" for k, v in sorted(rec.items())
            if k not in ("ts", "mono", "run_id", "kind", "name",
                         "event") and v is not None)
        click.echo("{:<20} {:<12} {:<24} {:<18} {}".format(
            stamp, rec.get("kind", "?"), str(rec.get("name", "?"))[:24],
            str(rec.get("event", "?"))[:18], detail))


@cli.command()
@click.argument("clusters", nargs=-1, required=False)
@click.option("--refresh", "-r", is_flag=True,
              help="Reconcile with provider truth.")
@click.option("--endpoints", is_flag=True,
              help="Show reachable endpoints for each cluster's opened "
                   "ports (reference: sky status --endpoints).")
@click.option("--events", "show_events", is_flag=True,
              help="Show recent lifecycle events (cluster/job/replica/"
                   "service transitions) from the observability log.")
@click.option("--limit", "-n", type=int, default=20,
              help="Max events with --events.")
@click.option("--since", default=None,
              help="With --events: only events newer than a duration "
                   "ago (30s/5m/2h/1d), a unix timestamp, or a local "
                   "YYYY-MM-DD[ HH:MM[:SS]] timestamp.")
def status(clusters, refresh, endpoints, show_events, limit, since):
    """List clusters (with launch age, head IP, and $/hr — reference:
    `sky status` table, sky/cli.py:1571)."""
    from skypilot_tpu import core
    if since and not show_events:
        raise click.UsageError("--since requires --events.")
    if show_events:
        if refresh or endpoints:
            raise click.UsageError(
                "--events cannot be combined with "
                "--refresh/--endpoints.")
        since_ts = None
        if since:
            from skypilot_tpu.observability import events as events_lib
            try:
                since_ts = events_lib.parse_since(since)
            except ValueError as e:
                raise click.UsageError(str(e)) from e
        # Filter BEFORE limiting: a busy neighbor's events at the tail
        # of the log must not evict the requested cluster's older ones.
        recs = core.recent_events(limit=None if clusters else limit,
                                  since=since_ts)
        if clusters:
            # Honor the positional filter: keep events whose subject
            # or recorded cluster/service matches a requested name.
            wanted = set(clusters)
            recs = [r for r in recs
                    if r.get("name") in wanted
                    or r.get("cluster") in wanted
                    or r.get("service") in wanted][-limit:]
        if not recs:
            click.echo("No recorded events.")
            return
        _print_events(recs)
        return
    records = core.status(cluster_names=list(clusters) or None,
                          refresh=refresh)
    if endpoints:
        from skypilot_tpu import provision as provision_api
        from skypilot_tpu.status_lib import ClusterStatus
        if not records:
            click.echo("No matching clusters.")
            return
        for r in records:
            handle = r["handle"]
            res = getattr(handle, "launched_resources", None)
            ports = list(res.ports) if res is not None else []
            if not ports:
                click.echo(f"{r['name']}: no opened ports")
                continue
            # Only an UP cluster has reachable addresses (reference:
            # sky status --endpoints errors for non-UP clusters).
            head = _head_ip(handle)
            if r["status"] != ClusterStatus.UP or head == "-":
                click.echo(f"{r['name']}: not UP — endpoints "
                           "unavailable")
                continue
            try:
                eps = provision_api.query_ports(
                    handle.provider_name, handle.cluster_name, ports,
                    head, handle.cluster_info.provider_config)
            except exceptions.SkyTpuError as e:
                click.echo(f"{r['name']}: {e}")
                continue
            if not eps:
                click.echo(f"{r['name']}: ports {ports} declared but "
                           "no ingress found (service deleted?)")
                continue
            for port in sorted(eps):
                click.echo(f"{r['name']}: {port} -> http://{eps[port]}")
        return
    if not records:
        click.echo("No existing clusters.")
        return
    fmt = "{:<20} {:<10} {:<28} {:<6} {:<10} {:>8} {:<15} {:>7}"
    click.echo(fmt.format("NAME", "LAUNCHED", "RESOURCES", "NODES",
                          "STATUS", "AUTOSTOP", "HEAD_IP", "$/HR"))
    for r in records:
        handle = r["handle"]
        res = getattr(handle, "launched_resources", None)
        autostop = f"{r['autostop']}m" if r["autostop"] >= 0 else "-"
        if r["autostop"] >= 0 and r.get("to_down"):
            autostop += "(down)"
        click.echo(fmt.format(
            r["name"], _human_ago(r.get("launched_at")),
            repr(res) if res else "-",
            getattr(handle, "num_slices", "-"),
            r["status"].value, autostop, _head_ip(handle),
            _price_per_hr(handle)))


def _glob_clusters(patterns) -> list:
    """Expand cluster-name glob patterns against recorded clusters
    (reference: _get_glob_clusters, sky/cli.py — `sky down "train-*"`).
    Literal names pass through even when unrecorded so the per-name
    error message still fires. Matching is fnmatchcase: cluster names
    are not file paths, so no platform case-folding (the reference's
    SQL GLOB is case-sensitive too)."""
    import fnmatch

    from skypilot_tpu import global_user_state
    known = [r["name"] for r in global_user_state.get_clusters()]
    out, seen = [], set()
    for pat in patterns:
        if any(c in pat for c in "*?["):
            matches = [n for n in known if fnmatch.fnmatchcase(n, pat)]
        else:
            matches = [pat]
        if not matches:
            click.echo(f"No clusters match {pat!r}.")
        for name in matches:
            if name not in seen:
                seen.add(name)
                out.append(name)
    return out


@cli.command()
@click.argument("clusters", nargs=-1, required=True)
def stop(clusters):
    """Stop cluster(s) (single-host slices only; pods are down-only).
    Names may be glob patterns ("train-*")."""
    from skypilot_tpu import core
    for name in _glob_clusters(clusters):
        try:
            core.stop(name)
            click.echo(f"Stopped {name}.")
        except exceptions.SkyTpuError as e:
            raise click.ClickException(str(e)) from e


@cli.command()
@click.argument("clusters", nargs=-1, required=True)
def start(clusters):
    """Restart stopped cluster(s). Names may be glob patterns."""
    from skypilot_tpu import core
    for name in _glob_clusters(clusters):
        try:
            core.start(name)
        except exceptions.SkyTpuError as e:
            raise click.ClickException(str(e)) from e
        click.echo(f"Started {name}.")


@cli.command()
@click.argument("clusters", nargs=-1, required=True)
@click.option("--purge", is_flag=True,
              help="Remove state even if cloud teardown fails.")
@click.option("--yes", "-y", is_flag=True)
def down(clusters, purge, yes):
    """Terminate cluster(s). Names may be glob patterns ("train-*")."""
    from skypilot_tpu import core
    names = _glob_clusters(clusters)
    if not names:
        return
    if not yes:
        click.confirm(f"Terminate {', '.join(names)}?", abort=True)
    failures = []
    for name in names:
        # One bad name (typo alongside a glob) must not strand the
        # clusters after it in the expanded list.
        try:
            core.down(name, purge=purge)
        except exceptions.SkyTpuError as e:
            failures.append(f"{name}: {e}")
            continue
        click.echo(f"Terminated {name}.")
    if failures:
        raise click.ClickException("; ".join(failures))


@cli.command()
@click.argument("cluster", required=True)
@click.option("--idle-minutes", "-i", type=int, required=True,
              help="Idle minutes before stopping; -1 cancels.")
@click.option("--down", "down_after", is_flag=True,
              help="Terminate instead of stop.")
def autostop(cluster, idle_minutes, down_after):
    """Schedule automatic stop/teardown on idleness."""
    from skypilot_tpu import core
    core.autostop(cluster, idle_minutes, down_after=down_after)
    if idle_minutes < 0:
        click.echo(f"Autostop cancelled for {cluster}.")
    else:
        click.echo(f"{cluster}: autostop after {idle_minutes} idle "
                   f"minutes ({'down' if down_after else 'stop'}).")


@cli.command()
@click.argument("cluster", required=True)
@click.option("--all-jobs", "-a", is_flag=True, default=False,
              help="Include finished jobs.")
def queue(cluster, all_jobs):
    """Show the cluster's job queue (reference `sky queue` columns:
    ID/NAME/SUBMITTED/STARTED/DURATION/STATUS)."""
    from skypilot_tpu import core
    jobs = core.queue(cluster, all_jobs=all_jobs)
    fmt = "{:<6} {:<20} {:<10} {:<12} {:<12} {:<10} {:<10}"
    click.echo(fmt.format("ID", "NAME", "USER", "SUBMITTED", "STARTED",
                          "DURATION", "STATUS"))
    import time as time_lib
    for j in jobs:
        start, end = j.get("start_at"), j.get("end_at")
        if start:
            dur = int((end or time_lib.time()) - start)
            duration = (f"{dur // 3600}h{(dur % 3600) // 60}m"
                        if dur >= 3600 else
                        f"{dur // 60}m{dur % 60}s" if dur >= 60
                        else f"{dur}s")
        else:
            duration = "-"
        click.echo(fmt.format(
            j["job_id"], j["job_name"] or "-", j["username"] or "-",
            _human_ago(j.get("submitted_at")),
            _human_ago(start) if start else "-", duration,
            j["status"]))


@cli.command()
@click.argument("cluster", required=True)
@click.argument("job_id", required=False, type=int)
@click.option("--no-follow", is_flag=True)
@click.option("--sync-down", is_flag=True,
              help="Download the job's log files instead of tailing.")
def logs(cluster, job_id, no_follow, sync_down):
    """Tail a job's logs (latest job if no id given)."""
    from skypilot_tpu import core
    if sync_down:
        got = core.download_logs(cluster,
                                 [job_id] if job_id is not None else None)
        for jid, path in sorted(got.items()):
            click.echo(f"job {jid}: {path}")
        if not got:
            click.echo(f"No logs to download"
                       + (f" for job {job_id}" if job_id is not None
                          else "") + f" on {cluster}.")
        sys.exit(0 if got else 1)
    sys.exit(core.tail_logs(cluster, job_id, follow=not no_follow))


@cli.command()
@click.argument("cluster", required=True)
@click.argument("job_ids", nargs=-1, type=int)
@click.option("--all", "-a", "all_jobs", is_flag=True)
def cancel(cluster, job_ids, all_jobs):
    """Cancel job(s)."""
    from skypilot_tpu import core
    done = core.cancel(cluster, list(job_ids) or None, all_jobs=all_jobs)
    click.echo(f"Cancelled jobs: {done or 'none'}")


@cli.command(name="show-tpus")
@click.argument("name_filter", required=False)
@click.option("--region", default=None)
def show_tpus(name_filter, region):
    """List TPU slice types, zones and prices (analog: sky show-gpus)."""
    from skypilot_tpu import catalog
    rows = catalog.list_accelerators(name_filter=name_filter,
                                     region_filter=region)
    fmt = "{:<14} {:>6} {:>6} {:<18} {:>12} {:>12}"
    click.echo(fmt.format("SLICE", "CHIPS", "HOSTS", "ZONE", "$/HR",
                          "SPOT $/HR"))
    for r in rows:
        click.echo(fmt.format(
            r["accelerator"], r["chips"], r["hosts"], r["zone"],
            f"{r['price']:.2f}", f"{r['spot_price']:.2f}"))


@cli.command()
@click.argument("paths", nargs=-1, type=click.Path(exists=True))
@click.option("--rule", "rules", multiple=True,
              help="Run only these rule ids (repeatable), e.g. "
                   "--rule stpu-donation.")
@click.option("--json", "as_json", is_flag=True,
              help="Machine-readable findings "
                   '([{"path","line","rule","message"}]).')
@click.option("--list-rules", is_flag=True,
              help="List registered rule ids and exit.")
@click.option("--env-table", is_flag=True,
              help="Emit the STPU_* env-knob table (markdown) from "
                   "utils/env_contract.py and exit.")
@click.option("--clouds", is_flag=True,
              help="Probe provider credentials instead (the legacy "
                   "`stpu check` behavior).")
def check(paths, rules, as_json, list_rules, env_table, clouds):
    """Static analysis: run the stpu-* rule suite over skypilot_tpu/
    (or PATHS). Exit 1 on findings. See docs/static-analysis.md for
    the rule catalog and the `# noqa: stpu-<rule> <reason>`
    suppression grammar. `--clouds` keeps the old credential probe."""
    if clouds:
        from skypilot_tpu import check as check_lib
        enabled = check_lib.check()
        click.echo(f"Enabled clouds: {', '.join(enabled) or 'none'}")
        return
    from skypilot_tpu import analysis
    if env_table:
        from skypilot_tpu.utils import env_contract
        click.echo(env_contract.render_markdown_table())
        return
    if list_rules:
        for rule in analysis.all_rules():
            click.echo(f"{rule.id}: {rule.title}")
        return
    try:
        findings = analysis.run_check(paths=list(paths) or None,
                                      rules=list(rules) or None)
    except KeyError as e:
        raise click.ClickException(str(e.args[0]))
    if as_json:
        from skypilot_tpu.analysis import core as analysis_core
        click.echo(analysis_core.render_json(findings))
    else:
        for f in findings:
            click.echo(f.render())
        n_rules = len(rules) if rules else len(analysis.all_rules())
        click.echo(f"{len(findings)} finding(s) from {n_rules} "
                   "rule(s).")
    if findings:
        raise SystemExit(1)


@cli.command()
@click.option("--family", "families", multiple=True,
              type=click.Choice(["llama", "mixtral", "gemma"]),
              help="Model families to sweep (repeatable; default "
                   "all three).")
@click.option("--mode", "modes", multiple=True,
              type=click.Choice(["paged", "spec", "q8"]),
              help="Engine modes to sweep (repeatable; default all). "
                   "Each mode tunes its own axes: paged/q8 = chunk x "
                   "gather window, spec = draft depth.")
@click.option("--out", type=click.Path(), default=None,
              help="Manifest output path (default "
                   "~/.stpu/tuning/manifest.json, where the engine "
                   "auto-loads it on the next start).")
@click.option("--quick", is_flag=True,
              help="Small step budgets: a fast, noisier sweep for "
                   "smoke tests and CI.")
@click.option("--tiny", is_flag=True,
              help="Sweep .tiny() model configs (CPU-friendly; the "
                   "constants tuned this way are NOT representative "
                   "of real model shapes — use for plumbing tests).")
@click.option("--slots", type=int, default=8, show_default=True,
              help="Engine slot count to tune for (keys the manifest "
                   "entry's batch band).")
def tune(families, modes, out, quick, tiny, slots):
    """Autotune decode-engine constants into a sha-pinned manifest.

    Sweeps the hand-pinned constants (prefill chunk / KV block
    size, gather window, speculative draft depth) per (family, batch
    band, tp, quant mode), measuring each candidate through the same
    decode_bench legs `stpu bench` records, pruning losers at a small
    step budget,
    and parity-gating every winner (greedy + seeded engine output
    must be bit-identical to default constants) before persisting.
    Engines pick the manifest up at startup; see STPU_TUNE_MANIFEST
    in docs/static-analysis.md and the Autotuning section of
    docs/performance.md."""
    import pathlib

    from skypilot_tpu.tune import sweep as tune_sweep
    doc = tune_sweep.run_sweep(
        families=list(families) or tune_sweep.FAMILIES,
        modes=list(modes) or tune_sweep.MODES,
        quick=quick, slots=slots, tiny=tiny,
        out_path=pathlib.Path(out) if out else None,
        log=click.echo)
    prov = doc["payload"]["provenance"]
    click.echo(f"manifest sha {doc['sha256'][:12]}  device "
               f"{prov['device_kind']}  commit {prov['commit']}")


def _resolve_service_url(url, service):
    """Shared --url/--service endpoint resolution (metrics/perf/
    profile): explicit URL wins, a service name resolves to its LB
    endpoint, neither returns None (local rendering)."""
    if url is not None:
        return url
    if service is not None:
        from skypilot_tpu.serve import core as serve_core
        matches = serve_core.status([service])
        if not matches:
            raise click.ClickException(
                f"Service {service!r} not found.")
        return matches[0]["endpoint"]
    return None


def _watch_render(render_once, watch: bool,
                  interval: float = 2.0) -> None:
    """The shared --watch loop (`stpu metrics`, `stpu perf`,
    `stpu top`): render once, or clear-screen + re-render every
    ``interval`` seconds until Ctrl-C — which exits cleanly, not with
    a traceback (the interrupt is how a watch is MEANT to end)."""
    if not watch:
        render_once()
        return
    import time as time_lib
    try:
        while True:
            click.clear()
            render_once()
            time_lib.sleep(interval)
    except KeyboardInterrupt:
        pass


def _counter_samples(text: str) -> dict:
    """``{series-id: value}`` for every counter-family sample in an
    exposition document. Series ids are the literal ``name{labels}``
    text — canonical in our renderer, so two scrapes key identically."""
    out: dict = {}
    family, kind = None, None
    for line in text.splitlines():
        if line.startswith("# TYPE "):
            parts = line.split()
            family = parts[2] if len(parts) > 2 else None
            kind = parts[3] if len(parts) > 3 else "untyped"
            continue
        if not line or line.startswith("#"):
            continue
        if kind != "counter" or family is None:
            continue
        sid, _, val = line.rpartition(" ")
        if not sid or not sid.startswith(family):
            continue
        try:
            out[sid] = float(val)
        except ValueError:
            continue
    return out


def _annotate_counter_rates(text: str, prev: dict, dt: float) -> str:
    """Append per-interval rates (``(+delta/dt /s)``) to counter
    sample lines — raw ``*_total`` values only show that traffic ever
    happened; under --watch the rate is what the operator is looking
    for. Gauges/histograms pass through untouched; a counter reset
    (process restart) shows ``(reset)`` instead of a negative rate."""
    if not prev or dt <= 0:
        return text
    lines = []
    for line in text.splitlines():
        sid, _, val = line.rpartition(" ")
        # `sid in prev` suffices: prev only holds counter series ids
        # (a family cannot change type between scrapes), so no second
        # parse of the current document is needed.
        if sid in prev:
            try:
                delta = float(val) - prev[sid]
            except ValueError:
                delta = None
            if delta is not None:
                line = (f"{line}  (reset)" if delta < 0
                        else f"{line}  (+{delta / dt:.4g}/s)")
        lines.append(line)
    return "\n".join(lines) + ("\n" if text.endswith("\n") else "")


@cli.command(name="metrics")
@click.option("--url", default=None,
              help="Scrape a remote /metrics endpoint (e.g. a serve "
                   "load balancer) instead of rendering locally.")
@click.option("--service", "-s", default=None,
              help="Scrape the named service's LB endpoint.")
@click.option("--watch", "-w", is_flag=True,
              help="Refresh until interrupted; counter families "
                   "additionally show the per-interval rate "
                   "(delta/dt) next to the cumulative value.")
@click.option("--interval", "-n", type=float, default=2.0,
              show_default=True,
              help="Refresh period for --watch, seconds.")
def metrics_cmd(url, service, watch, interval):
    """Render Prometheus metrics: the local registry by default, a serve
    LB's /metrics with --url/--service (same exposition `curl
    $LB/metrics` returns)."""
    import time as time_lib

    from skypilot_tpu import core

    # Resolve once: the endpoint cannot change mid-watch, and with
    # --service each resolution is a full serve status() call.
    target = _resolve_service_url(url, service)
    prev = {"samples": None, "mono": 0.0}

    def render_once():
        import http.client
        try:
            text = core.metrics_snapshot(target)
        except (OSError, ValueError, http.client.HTTPException) as e:
            # HTTPException covers http.client.InvalidURL from a
            # malformed --url; ValueError covers unknown URL types.
            # All must read as a scrape failure, not a crash.
            raise click.ClickException(f"scrape failed: {e}") from e
        now = time_lib.perf_counter()
        if watch:
            # Samples from the RAW text, before annotations land.
            samples = _counter_samples(text)
            if prev["samples"] is not None:
                text = _annotate_counter_rates(text, prev["samples"],
                                               now - prev["mono"])
            prev["samples"] = samples
            prev["mono"] = now
        click.echo(text if text.strip() else "(no metrics recorded)")

    _watch_render(render_once, watch, interval)


def _fmt_ms(seconds) -> str:
    if seconds is None:
        return "-"
    return f"{float(seconds) * 1000:.2f}ms"


def _perf_snapshot_lines(doc: dict, label: str = "") -> list:
    """Human rendering of one stepstats snapshot document."""
    lines = []
    head = f"perf{(' ' + label) if label else ''}"
    armed = "armed" if doc.get("armed") else "DISARMED (export " \
                                            "STPU_STEPSTATS=1)"
    lines.append(
        f"{head:<10} {armed}  steps {doc.get('steps', 0)}"
        f"/{doc.get('ring_size', 0)} in ring"
        f" ({doc.get('total_steps', 0)} total)"
        f"  window {doc.get('window_s', 0):.2f}s"
        f"  busy {doc.get('busy_fraction', 0) * 100:.1f}%")
    phases = doc.get("phases") or {}
    if phases:
        lines.append("{:<10} {:>8} {:>10} {:>7} ".format(
            "phase", "steps", "seconds", "share"))
        for p in ("prefill", "decode", "mixed"):
            d = phases.get(p)
            if not d:
                continue
            lines.append("{:<10} {:>8} {:>10.3f} {:>6.1f}%".format(
                p, d.get("steps", 0), d.get("seconds", 0.0),
                d.get("share", 0.0) * 100))
    tok = doc.get("tokens_per_sec") or {}
    if tok:
        lines.append(f"tok/s      prefill {tok.get('prefill', 0)}"
                     f"  decode {tok.get('decode', 0)}")
    spec = doc.get("spec") or {}
    if spec:
        lines.append(
            f"spec       drafted {spec.get('drafted', 0)}"
            f"  accepted {spec.get('accepted', 0)}"
            f"  accept {spec.get('accept_rate', 0.0) * 100:.1f}%")
    occ = doc.get("occupancy") or {}
    lines.append(f"slots      mean {occ.get('mean', 0)}  last "
                 f"{occ.get('last', 0)}  queue "
                 f"{doc.get('queue_depth', 0)}")
    if doc.get("kv_pool"):
        pool = doc["kv_pool"]
        lines.append(f"kv pool    free {pool.get('free')}"
                     f" / usable {pool.get('usable')} blocks (paged)")
    quant = doc.get("quant") or {}
    if quant.get("kv_quant") or quant.get("weight_quant"):
        modes = [m for m, on in (("kv int8", quant.get("kv_quant")),
                                 ("weights int8",
                                  quant.get("weight_quant"))) if on]
        lines.append(
            f"quant      {' + '.join(modes)}"
            + (f"  pool {quant.get('pool_blocks')} blocks"
               if quant.get("pool_blocks") else ""))
    if doc.get("dispatch_ms_mean") is not None or doc.get("sync"):
        sync = doc.get("sync") or {}
        lines.append(
            f"split      dispatch {doc.get('dispatch_ms_mean', '-')}"
            f"ms mean  device "
            f"{sync.get('device_ms_mean', '-')}ms mean"
            + (f" (sampled every {sync.get('every')} steps, "
               f"n={sync.get('samples')})" if sync else
               "  (device: set STPU_STEPSTATS_SYNC_EVERY=N)"))
    tier = doc.get("tier") or {}
    if tier:
        lines.append(
            f"kv tier    host {tier.get('blocks', 0)} blocks"
            f" / {tier.get('bytes', 0) / (1 << 20):.1f}"
            f"/{tier.get('budget_mb', 0):.0f} MiB"
            f"  spilled {tier.get('spilled', 0)}"
            f"  dropped {tier.get('dropped', 0)}"
            f"  readmitted {tier.get('readmitted', 0)}"
            f"  rehits {tier.get('rehits', 0)}")
    tuning = doc.get("tuning") or {}
    if tuning:
        lines.append(
            f"tuning     block {tuning.get('block', 0)}"
            f"  chunk {tuning.get('chunk', 0)}"
            f"  window {tuning.get('window', 0)}"
            f"  spec_k {tuning.get('spec_k', 0)}"
            f"  manifest {tuning.get('manifest', 'default')}")
    eng = doc.get("engine") or {}
    if eng:
        lines.append(
            f"engine     {'healthy' if eng.get('healthy') else 'DOWN'}"
            f"  in_flight {eng.get('in_flight', 0)}"
            f"  restarts {eng.get('restarts', 0)}"
            + ("  draining" if eng.get("draining") else ""))
    return lines


def _render_perf_doc(doc: dict) -> str:
    """Render a replica /perf snapshot OR the LB's merged
    {replicas, aggregate} document."""
    if "replicas" in doc and isinstance(doc.get("replicas"), dict):
        lines = []
        agg = doc.get("aggregate") or {}
        lines.append(f"merged     {agg.get('replicas', 0)} replica(s)")
        tok = agg.get("tokens_per_sec") or {}
        if tok:
            lines.append(
                f"tok/s      prefill {tok.get('prefill', 0)}"
                f"  decode {tok.get('decode', 0)}"
                + (f"  busy {agg['busy_fraction_mean'] * 100:.1f}%"
                   if agg.get("busy_fraction_mean") is not None
                   else ""))
        for url in sorted(doc["replicas"]):
            lines.append("")
            lines.extend(_perf_snapshot_lines(doc["replicas"][url],
                                              label=url))
        return "\n".join(lines)
    return "\n".join(_perf_snapshot_lines(doc))


class _PerfGroup(click.Group):
    """`stpu perf SERVICE` — a leading token that is not a subcommand
    is the service name for the default snapshot action (the ISSUE-
    shaped UX), rewritten to `--service` before normal parsing."""

    def parse_args(self, ctx, args):
        if args and not args[0].startswith("-") \
                and args[0] not in self.commands:
            args = ["--service", args[0]] + list(args[1:])
        return super().parse_args(ctx, args)


@cli.group(name="perf", cls=_PerfGroup, invoke_without_command=True)
@click.option("--service", "-s", default=None,
              help="Service whose LB /perf to fetch (also accepted "
                   "as a bare leading argument: `stpu perf svc`).")
@click.option("--url", default=None,
              help="Fetch a replica's (or LB's) /perf endpoint "
                   "directly.")
@click.option("--watch", "-w", is_flag=True,
              help="Refresh until interrupted.")
@click.option("--interval", "-n", type=float, default=2.0,
              show_default=True,
              help="Refresh period for --watch, seconds.")
@click.pass_context
def perf(ctx, service, url, watch, interval):
    """Per-step engine performance telemetry (arm with
    STPU_STEPSTATS=1 on the replicas).

    Fetches the step-ring snapshot — phase breakdown (prefill vs
    decode), busy fraction, slot occupancy, sampled dispatch-vs-device
    split, KV-pool state — from a replica's GET /perf or the LB's
    merged view. See docs/observability.md."""
    if ctx.invoked_subcommand is not None:
        return
    from skypilot_tpu import core
    target = _resolve_service_url(url, service)
    if target is None:
        raise click.UsageError(
            "give a SERVICE or --url (or use `stpu perf dump|show` "
            "for flight-recorder dumps).")

    def render_once():
        import http.client
        try:
            doc = core.perf_snapshot(target)
        except (OSError, ValueError, http.client.HTTPException) as e:
            raise click.ClickException(f"fetch failed: {e}") from e
        click.echo(_render_perf_doc(doc))

    _watch_render(render_once, watch, interval)


@perf.command(name="dump")
@click.argument("run", required=False)
def perf_dump(run):
    """Flight-recorder dumps: list them (no RUN), or print one dump's
    raw JSON. RUN may be a file name, a unique prefix, or a path."""
    import json as json_lib
    import time as time_lib

    from skypilot_tpu.observability import stepstats
    if run is None:
        dumps = stepstats.list_dumps()
        if not dumps:
            click.echo("No flight-recorder dumps (arm "
                       "STPU_STEPSTATS=1; dumps are written on engine "
                       "crash/restart and SIGTERM).")
            return
        click.echo("{:<52} {:<14} {:<20}".format(
            "DUMP", "REASON", "WHEN"))
        for name in dumps:
            try:
                doc = stepstats.read_dump(name)
            except (OSError, ValueError):
                continue
            stamp = time_lib.strftime(
                "%Y-%m-%d %H:%M:%S",
                time_lib.localtime(doc.get("ts", 0)))
            click.echo("{:<52} {:<14} {:<20}".format(
                name, doc.get("reason", "?"), stamp))
        return
    try:
        doc = stepstats.read_dump(run)
    except (OSError, ValueError) as e:
        raise click.ClickException(str(e)) from e
    click.echo(json_lib.dumps(doc, indent=1, default=str))


@perf.command(name="show")
@click.argument("run", required=False)
@click.option("--steps", "-n", type=int, default=10,
              help="Step records shown from the tail of the ring.")
def perf_show(run, steps):
    """Render one flight-recorder dump: trigger, terminal exception,
    aggregate phase breakdown, and the last step/admission records.
    RUN defaults to the newest dump."""
    import time as time_lib

    from skypilot_tpu.observability import stepstats
    try:
        doc = stepstats.read_dump(run)
    except (OSError, ValueError) as e:
        raise click.ClickException(str(e)) from e
    stamp = time_lib.strftime("%Y-%m-%d %H:%M:%S",
                              time_lib.localtime(doc.get("ts", 0)))
    click.echo(f"dump       {doc.get('path', '-')}")
    click.echo(f"trigger    {doc.get('reason', '?')} at {stamp} "
               f"(run {doc.get('run_id', '-')}, pid "
               f"{doc.get('pid', '-')})")
    if doc.get("error"):
        click.echo(f"error      {doc['error']}")
    snap = doc.get("snapshot") or {}
    if snap:
        for line in _perf_snapshot_lines(snap):
            click.echo(line)
    recs = (doc.get("steps") or [])[-steps:] if steps > 0 else []
    if recs:
        click.echo(f"last {len(recs)} step(s):")
        click.echo("  {:>8} {:<8} {:>9} {:>6} {:>6} {:>6} {:>6}".format(
            "seq", "phase", "dur", "slots", "queue", "ptok", "dtok"))
        for r in recs:
            click.echo(
                "  {:>8} {:<8} {:>9} {:>6} {:>6} {:>6} {:>6}".format(
                    r.get("seq", "-"), r.get("phase", "?"),
                    _fmt_ms(r.get("dur")), r.get("live_slots", 0),
                    r.get("queue_depth", 0),
                    r.get("prefill_tokens", 0),
                    r.get("decode_tokens", 0)))
    admits = (doc.get("admissions") or [])[-5:]
    if admits:
        click.echo(f"last {len(admits)} admission(s) "
                   f"({len(doc.get('admissions') or [])} recorded):")
        for a in admits:
            click.echo(
                f"  slot {a.get('slot')}  prompt "
                f"{a.get('prompt_tokens')}  max {a.get('max_tokens')}"
                f"  cached {a.get('cached_tokens')}  wait "
                f"{_fmt_ms(a.get('queue_wait_s'))}")


@cli.command(name="profile")
@click.argument("service", required=False)
@click.option("--url", default=None,
              help="POST a replica's (or LB's) /profile endpoint "
                   "directly.")
@click.option("--seconds", "-t", type=float, default=5.0,
              show_default=True,
              help="Capture window (clamped to [0.05, 120]s "
                   "replica-side).")
def profile_cmd(service, url, seconds):
    """Capture an on-demand jax.profiler trace on a serving replica
    (written replica-side to ~/.stpu/logs/profiles/<stamp>/; load in
    TensorBoard / Perfetto alongside `stpu trace export`)."""
    import json as json_lib
    import urllib.request
    target = _resolve_service_url(url, service)
    if target is None:
        raise click.UsageError("give a SERVICE or --url.")
    if "://" not in target:
        target = f"http://{target}"
    endpoint = (target.rstrip("/")
                + f"/profile?seconds={float(seconds)}")
    req = urllib.request.Request(endpoint, data=b"", method="POST")
    try:
        with urllib.request.urlopen(req, timeout=30) as resp:
            doc = json_lib.loads(resp.read().decode("utf-8",
                                                    "replace"))
    except (OSError, ValueError) as e:
        raise click.ClickException(f"profile request failed: {e}") \
            from e
    click.echo(f"capturing {doc.get('seconds')}s of profile to "
               f"{doc.get('profile_dir')} (replica-side)")


def _fmt_val(v, fmt="{:.1f}", dash="-") -> str:
    """Format a fleet-store reading, rendering missing data (None —
    e.g. an empty histogram window whose quantile would be NaN) as
    ``-`` instead of crashing or printing nan."""
    if v is None:
        return dash
    try:
        return fmt.format(float(v))
    except (TypeError, ValueError):
        return dash


def _slo_lines(slo: dict) -> list:
    lines = []
    if not slo or not slo.get("objectives"):
        lines.append("slo        (no objectives declared — add a "
                     "service.slo section to the YAML)")
        return lines
    lines.append(
        "slo        fast {}s / slow {}s windows, breach at burn >= {}"
        .format(int(slo.get("fast_window_s", 0)),
                int(slo.get("slow_window_s", 0)),
                slo.get("burn_threshold", 1.0)))
    lines.append("{:<12} {:>8} {:>10} {:>10} {:>10} {:>9}  {}".format(
        "OBJECTIVE", "TARGET", "THRESHOLD", "BURN-FAST", "BURN-SLOW",
        "BUDGET", "STATE"))
    for obj in slo["objectives"]:
        lines.append(
            "{:<12} {:>8} {:>10} {:>10} {:>10} {:>9}  {}".format(
                obj.get("kind", "?"),
                _fmt_val(obj.get("target"), "{:.3g}"),
                (_fmt_ms(obj.get("threshold_seconds"))
                 if obj.get("threshold_seconds") is not None else "-"),
                _fmt_val(obj.get("burn_fast"), "{:.2f}"),
                _fmt_val(obj.get("burn_slow"), "{:.2f}"),
                _fmt_val(obj.get("budget_remaining"), "{:.1%}"),
                "BREACHING" if obj.get("breaching") else "ok"))
    return lines


def _render_fleet_doc(doc: dict) -> str:
    """Human rendering of the GET /fleet document (`stpu top`)."""
    import time as time_lib
    lines = []
    collected = doc.get("collected_at")
    age = (f"{max(0.0, time_lib.time() - collected):.1f}s ago"
           if collected else "never")
    scaler = doc.get("autoscaler") or {}
    lines.append(
        f"fleet      {doc.get('service', '?')}  collected {age}  "
        f"window {int(doc.get('window_s', 0))}s  "
        f"policy {scaler.get('policy', '-')}  "
        f"target {scaler.get('target', '-')} "
        f"(qps {_fmt_val(scaler.get('qps'), '{:.2f}')})")
    lb = doc.get("lb") or {}
    ttfb = lb.get("ttfb") or {}
    lines.append(
        f"edge       ttfb p50 {_fmt_ms(ttfb.get('p50'))}"
        f"  p99 {_fmt_ms(ttfb.get('p99'))}"
        f"  (n={int(ttfb.get('count') or 0)})"
        f"  rate {_fmt_val(lb.get('request_rate'), '{:.2f}')}/s")
    slo = doc.get("slo")
    degraded = bool(slo and slo.get("degraded"))
    if slo:
        lines.extend(_slo_lines(slo))
    if degraded:
        lines.append("state      DEGRADED (SLO breaching)")
    replicas = doc.get("replicas") or {}
    if replicas:
        lines.append("")
        lines.append(
            "{:<44} {:>11} {:>6} {:>7} {:>6} {:>11} {:>9} {:>9}".format(
                "REPLICA", "TOK/S(P/D)", "BUSY", "SLOTS", "QUEUE",
                "POOL(F/T)", "TTFT-P50", "TTFT-P99"))
        for url in sorted(replicas):
            r = replicas[url]
            tok = r.get("tokens_per_sec") or {}
            decode = tok.get("decode")
            if decode is None:
                # Stepstats disarmed on the replica: fall back to the
                # counter-derived decode rate from the store.
                decode = r.get("decode_tokens_per_sec")
            slots = r.get("slots") or {}
            pool = r.get("kv_pool") or {}
            ttft = r.get("ttft") or {}
            lines.append(
                "{:<44} {:>11} {:>6} {:>7} {:>6} {:>11} {:>9} {:>9}"
                .format(
                    url,
                    f"{_fmt_val(tok.get('prefill'), '{:.0f}')}"
                    f"/{_fmt_val(decode, '{:.0f}')}",
                    _fmt_val(r.get("busy_fraction"), "{:.0%}"),
                    f"{_fmt_val(slots.get('occupied'), '{:.0f}')}"
                    f"/{_fmt_val(slots.get('total'), '{:.0f}')}",
                    _fmt_val(r.get("queue_depth"), "{:.0f}"),
                    f"{_fmt_val(pool.get('free'), '{:.0f}')}"
                    f"/{_fmt_val(pool.get('total'), '{:.0f}')}",
                    _fmt_ms(ttft.get("p50")), _fmt_ms(ttft.get("p99"))))
    else:
        lines.append("(no replica telemetry collected yet)")
    decision = scaler.get("last_decision")
    if decision:
        ts, qps, target, ready = (list(decision) + [None] * 4)[:4]
        stamp = time_lib.strftime("%H:%M:%S",
                                  time_lib.localtime(ts or 0))
        lines.append(
            f"last plan  target {target} (qps "
            f"{_fmt_val(qps, '{:.2f}')}, ready {ready}) at {stamp}")
    return "\n".join(lines)


@cli.command(name="top")
@click.argument("service", required=False)
@click.option("--url", default=None,
              help="Fetch a service endpoint's (or controller sync "
                   "server's) /fleet directly.")
@click.option("--watch", "-w", is_flag=True,
              help="Refresh until interrupted.")
@click.option("--interval", "-n", type=float, default=2.0,
              show_default=True,
              help="Refresh period for --watch, seconds.")
def top_cmd(service, url, watch, interval):
    """Live fleet view from the controller's telemetry store: per-
    replica tok/s, busy fraction, slot/pool occupancy, TTFT quantiles
    (histogram deltas over the SLO fast window), SLO budget, and the
    last scale decision. See docs/observability.md."""
    from skypilot_tpu import core
    target = _resolve_service_url(url, service)
    if target is None:
        raise click.UsageError("give a SERVICE or --url.")

    def render_once():
        import http.client
        try:
            doc = core.fleet_snapshot(target)
        except (OSError, ValueError, http.client.HTTPException) as e:
            raise click.ClickException(f"fetch failed: {e}") from e
        if doc.get("error"):
            raise click.ClickException(str(doc["error"]))
        click.echo(_render_fleet_doc(doc))

    _watch_render(render_once, watch, interval)


@cli.command(name="slo")
@click.argument("service", required=False)
@click.option("--url", default=None,
              help="Fetch a service endpoint's (or controller sync "
                   "server's) /fleet directly.")
def slo_cmd(service, url):
    """Per-objective SLO status: burn rates over the fast/slow
    windows, remaining error budget, and breach state (the burn-rate
    monitor over the fleet telemetry store — docs/observability.md)."""
    from skypilot_tpu import core
    target = _resolve_service_url(url, service)
    if target is None:
        raise click.UsageError("give a SERVICE or --url.")
    import http.client
    try:
        doc = core.fleet_snapshot(target)
    except (OSError, ValueError, http.client.HTTPException) as e:
        raise click.ClickException(f"fetch failed: {e}") from e
    if doc.get("error"):
        raise click.ClickException(str(doc["error"]))
    click.echo(f"service    {doc.get('service', '?')}")
    for line in _slo_lines(doc.get("slo") or {}):
        click.echo(line)
    if doc.get("slo") and doc["slo"].get("degraded"):
        click.echo("state      DEGRADED (SLO breaching)")


@cli.group(name="loadgen", invoke_without_command=True)
@click.option("--target", default=None,
              help="Endpoint to drive (a serve LB / serve_llm "
                   "--lb-port URL). Required unless a subcommand is "
                   "given.")
@click.option("--mix", type=click.Choice(["chat", "long_context",
                                          "bursty"]),
              default="chat", show_default=True,
              help="Workload shape: chat = shared system prompts + "
                   "unique tails; long_context = prefill-heavy; "
                   "bursty = chat under a diurnal rate wave.")
@click.option("--arrival", type=click.Choice(["poisson", "ramp",
                                              "uniform"]),
              default="poisson", show_default=True,
              help="Arrival process (open loop: requests fire on "
                   "schedule regardless of completions).")
@click.option("--qps", type=float, default=8.0, show_default=True,
              help="Base offered arrival rate.")
@click.option("--duration", type=float, default=10.0,
              show_default=True, help="Trace length in seconds.")
@click.option("--seed", type=int, default=0, show_default=True,
              help="Schedule seed: the same seed replays the trace "
                   "bit-identically (arrivals, prompts, budgets).")
@click.option("--max-tokens", type=int, default=32, show_default=True)
@click.option("--prompt-tokens", type=int, default=96,
              show_default=True,
              help="Mean total chat prompt length.")
@click.option("--shared-prefix", type=int, default=64,
              show_default=True,
              help="Tokens per shared system prompt (chat/bursty).")
@click.option("--slo-ttft", type=float, default=None,
              help="TTFT SLO in seconds; requests above it do not "
                   "count toward goodput.")
@click.option("--slo-tpot", type=float, default=None,
              help="Per-output-token latency SLO in seconds.")
@click.option("--scrape-interval", type=float, default=1.0,
              show_default=True,
              help="Seconds between /metrics snapshots into the "
                   "run's metrics.jsonl time series.")
@click.option("--faults", default=None,
              help="STPU_FAULTS-grammar chaos spec armed mid-run in "
                   "THIS process (in-process stacks; remote stacks "
                   "export STPU_FAULTS themselves), e.g. "
                   "'lb.upstream:delay:s=0.5'.")
@click.option("--faults-at", type=float, default=0.0,
              show_default=True,
              help="Seconds into the run to arm --faults.")
@click.option("--out", default=None,
              help="Run directory (default "
                   "~/.stpu/logs/loadgen/<stamp>-<mix>-seed<seed>).")
@click.option("--schedule", "schedule_file", default=None,
              type=click.Path(exists=True, dir_okay=False),
              help="Replay a saved schedule.json verbatim (a prior "
                   "run's artifact or `stpu loadgen capture` output); "
                   "overrides every spec knob, the report records "
                   "source=schedule + the pinned digest.")
@click.option("--json", "as_json", is_flag=True,
              help="Print the raw report JSON instead of the "
                   "rendered summary.")
@click.pass_context
def loadgen(ctx, target, mix, arrival, qps, duration, seed, max_tokens,
            prompt_tokens, shared_prefix, slo_ttft, slo_tpot,
            scrape_interval, faults, faults_at, out, schedule_file,
            as_json):
    """Trace-driven open-loop load harness with SLO reports.

    Fires a seeded, replayable request schedule at a live serving
    endpoint while snapshotting its /metrics into a run-scoped JSONL
    time series, then reports TTFT/TPOT/e2e percentiles (client-side
    AND interpolated from the server's histograms), achieved vs
    offered QPS, error/retry/breaker counts, and goodput under the
    declared SLOs. See docs/observability.md."""
    if ctx.invoked_subcommand is not None:
        return
    if not target:
        raise click.UsageError(
            "--target is required (or use `stpu loadgen report`).")
    import json as json_lib

    from skypilot_tpu.benchmark import loadgen as loadgen_lib
    try:
        spec = None
        if schedule_file is None:
            spec = loadgen_lib.LoadSpec(
                mix=mix, arrival=arrival, qps=qps, duration_s=duration,
                seed=seed, max_tokens=max_tokens,
                prompt_tokens=prompt_tokens,
                shared_prefix=shared_prefix)
        report = loadgen_lib.run(
            target, spec, slo_ttft_s=slo_ttft, slo_tpot_s=slo_tpot,
            scrape_interval=scrape_interval, out_dir=out,
            faults=faults, faults_at=faults_at,
            schedule_file=schedule_file)
    except (ValueError, OSError) as e:
        raise click.ClickException(str(e)) from e
    if as_json:
        click.echo(json_lib.dumps(report, indent=1))
    else:
        click.echo(loadgen_lib.format_report(report))


@loadgen.command(name="report")
@click.argument("run", required=False)
@click.option("--json", "as_json", is_flag=True,
              help="Print the raw report JSON.")
def loadgen_report(run, as_json):
    """Render a recorded run's SLO report. RUN is a run directory or a
    name under ~/.stpu/logs/loadgen/; defaults to the newest run."""
    import json as json_lib
    import os as os_lib

    from skypilot_tpu.benchmark import loadgen as loadgen_lib
    if run is None:
        run_dir = loadgen_lib.latest_run_dir()
        if run_dir is None:
            raise click.ClickException(
                "No recorded loadgen runs (run `stpu loadgen "
                "--target ...` first).")
    elif os_lib.path.isdir(run):
        run_dir = run
    else:
        run_dir = os_lib.path.join(loadgen_lib.runs_root(), run)
    report_path = os_lib.path.join(run_dir, "report.json")
    try:
        with open(report_path) as f:
            report = json_lib.load(f)
    except (OSError, ValueError) as e:
        raise click.ClickException(
            f"cannot read {report_path}: {e}") from e
    report.setdefault("out_dir", run_dir)
    if as_json:
        click.echo(json_lib.dumps(report, indent=1))
    else:
        click.echo(loadgen_lib.format_report(report))


@loadgen.command(name="capture")
@click.option("--from", "source", "--from-file", default=None,
              type=click.Path(exists=True, dir_okay=False),
              help="requests.jsonl to derive from (default "
                   "~/.stpu/logs/requests.jsonl).")
@click.option("--out", default="schedule.json", show_default=True,
              help="Where to write the derived schedule.json.")
@click.option("--since", type=float, default=None,
              help="Only use records from the last SINCE seconds.")
def loadgen_capture(source, out, since):
    """Derive a replayable schedule.json from captured request
    records.

    Fits the arrival rate/burstiness, prompt-length distribution,
    max-tokens budget, and prefix-reuse structure of the wide-event
    records (observability/reqlog.py — arm the serving stack with
    STPU_REQLOG=1 first) into a synthesized LoadSpec, then freezes it
    into a bit-identically-replayable schedule: the derivation is
    deterministic, so the same records always produce the same
    digest. Replay with `stpu loadgen --target ... --schedule FILE`.
    Records carry only a leading-chunk hash — no prompt text rides
    along; replayed prompts are synthetic with matching shape."""
    import time as time_lib

    from skypilot_tpu.benchmark import loadgen as loadgen_lib
    from skypilot_tpu.observability import reqlog
    records = reqlog.read(path=source)
    if since is not None:
        cutoff = time_lib.time() - since
        records = [r for r in records
                   if isinstance(r.get("ts"), (int, float))
                   and r["ts"] >= cutoff]
    try:
        spec = loadgen_lib.derive_spec(records)
        schedule = loadgen_lib.build_schedule(spec)
        digest = loadgen_lib.save_schedule(out, spec, schedule)
    except (ValueError, OSError) as e:
        raise click.ClickException(str(e)) from e
    click.echo(f"Derived {len(schedule)} requests from "
               f"{len(records)} records "
               f"(mix={spec.mix} qps={spec.qps:.2f} "
               f"duration={spec.duration_s:.1f}s "
               f"prompt_tokens={spec.prompt_tokens}).")
    click.echo(f"Wrote {out} (digest {digest[:16]}). Replay with "
               f"`stpu loadgen --target URL --schedule {out}`.")


def _requests_rows(url, since, status, slow, replica):
    """Fetch + filter wide-event request records for `stpu requests`:
    from the LB's /requests endpoint when a URL is known, else the
    local ~/.stpu/logs/requests.jsonl."""
    import time as time_lib

    from skypilot_tpu.observability import reqlog
    if url is not None:
        import json as json_lib
        import urllib.request as urllib_request
        try:
            with urllib_request.urlopen(
                    url.rstrip("/") + "/requests", timeout=5.0) as r:
                rows = json_lib.load(r)
        except Exception as e:
            raise click.ClickException(
                f"cannot fetch {url}/requests: {e}") from e
        rows = [r for r in rows if isinstance(r, dict)]
    else:
        rows = reqlog.read()
    if since is not None:
        cutoff = time_lib.time() - since
        rows = [r for r in rows
                if isinstance(r.get("ts"), (int, float))
                and r["ts"] >= cutoff]
    if status is not None:
        rows = [r for r in rows if str(r.get("status")) == status]
    if slow:
        rows = [r for r in rows if reqlog.is_slow(r)]
    if replica is not None:
        rows = [r for r in rows
                if str(r.get("replica", "")).find(replica) >= 0]
    return rows


@cli.group(name="requests", cls=_PerfGroup,
           invoke_without_command=True)
@click.option("--service", "-s", default=None,
              help="Service whose LB /requests to fetch (also "
                   "accepted as a bare leading argument: "
                   "`stpu requests svc`).")
@click.option("--url", default=None,
              help="Explicit LB endpoint (e.g. "
                   "http://127.0.0.1:8080); reads its /requests "
                   "endpoint instead of the local log.")
@click.option("--since", type=float, default=None,
              help="Only records from the last SINCE seconds.")
@click.option("--status", default=None,
              help="Filter on final status (200, 503, "
                   "upstream_aborted, ...).")
@click.option("--slow", is_flag=True,
              help="Only records over the slow thresholds "
                   "(STPU_REQLOG_SLOW_TTFT / _SLOW_E2E).")
@click.option("--replica", default=None,
              help="Substring filter on the serving replica.")
@click.option("--limit", "-n", type=int, default=30,
              show_default=True, help="Max records shown (newest "
                                      "last).")
@click.option("--json", "as_json", is_flag=True,
              help="Raw record JSON, one per line.")
@click.pass_context
def requests_cmd(ctx, service, url, since, status, slow, replica,
                 limit, as_json):
    """Per-request wide-event analytics (arm with STPU_REQLOG=1).

    One joined record per request — the LB half (policy pick,
    retries, resume outcome, client TTFT/e2e) folded with the
    engine half (queue wait, token counts, KV tier, speculative
    accept counts, per-request device-time share). Tail-biased:
    errors, resumed streams, and slow requests are always kept even
    when STPU_REQLOG_SAMPLE thins the rest. See
    docs/observability.md."""
    if ctx.invoked_subcommand is not None:
        return
    import json as json_lib
    import time as time_lib
    rows = _requests_rows(_resolve_service_url(url, service), since,
                          status, slow, replica)
    if not rows:
        click.echo("No request records (arm the serving stack with "
                   "STPU_REQLOG=1).")
        return
    rows = rows[-limit:]
    if as_json:
        for r in rows:
            click.echo(json_lib.dumps(r, default=str))
        return
    fmt = "{:<10} {:<19} {:>6} {:>8} {:>8} {:>6} {:<8} {}"
    click.echo(fmt.format("REQUEST", "STARTED", "STATUS", "TTFT",
                          "E2E", "TOKENS", "KEEP", "REPLICA"))
    for r in rows:
        ts = r.get("ts")
        stamp = (time_lib.strftime("%Y-%m-%d %H:%M:%S",
                                   time_lib.localtime(ts))
                 if isinstance(ts, (int, float)) else "-")
        eng = r.get("engine") or {}
        ttft = r.get("ttft_s")
        e2e = r.get("e2e_s")
        click.echo(fmt.format(
            str(r.get("request_id", "?"))[:8],
            stamp, str(r.get("status", "?")),
            _fmt_dur(ttft) if isinstance(ttft, (int, float)) else "-",
            _fmt_dur(e2e) if isinstance(e2e, (int, float)) else "-",
            eng.get("generated_tokens", "-"),
            r.get("keep") or "-",
            r.get("replica") or "-"))


@requests_cmd.command(name="show")
@click.argument("request_id", required=True)
def requests_show(request_id):
    """Render one joined request record in full. REQUEST_ID may be
    abbreviated; cross-links `stpu trace show` when the request's
    trace was sampled."""
    from skypilot_tpu.observability import reqlog
    rows = reqlog.read(request_id=request_id)
    if not rows:
        raise click.ClickException(
            f"No request record matches {request_id!r}.")
    ids = {r.get("request_id") for r in rows}
    if len(ids) > 1:
        raise click.ClickException(
            f"{request_id!r} is ambiguous ({len(ids)} requests); "
            "give more characters.")
    rec = rows[-1]
    rid = rec.get("request_id", "?")
    click.echo(f"request {rid}")
    eng = rec.get("engine") or {}
    order = ("ts", "method", "path", "status", "keep", "replica",
             "policy", "attempts", "retries", "resumed",
             "resume_outcome", "ttft_s", "e2e_s", "bytes_streamed",
             "prompt_tokens", "max_tokens", "temperature", "stream",
             "prefix_hash", "trace_sampled")
    for key in order:
        if key in rec:
            click.echo(f"  {key:<18} {rec[key]}")
    for key in sorted(rec):
        if key not in order and key not in ("request_id", "engine"):
            click.echo(f"  {key:<18} {rec[key]}")
    if eng:
        click.echo("  engine:")
        for key in sorted(eng):
            click.echo(f"    {key:<16} {eng[key]}")
    else:
        click.echo("  engine:            (none — LB-only record: "
                   "legacy replica or stream never reached the "
                   "trailing stats event)")
    if rec.get("trace_sampled"):
        click.echo(f"  trace was sampled — `stpu trace show {rid}` "
                   "has the span tree.")


@cli.group(name="trace")
def trace():
    """Distributed request/launch traces (arm with STPU_TRACE=1).

    Spans are recorded to ~/.stpu/logs/traces.jsonl by every traced
    process on this host: the serve LB's per-request root span, the
    replica/decode-engine children it propagates to via X-STPU-Trace,
    and jobs-controller/gang-driver launch spans."""


def _fmt_dur(seconds: float) -> str:
    if seconds >= 1.0:
        return f"{seconds:.3f}s"
    return f"{seconds * 1000:.1f}ms"


def _resolve_trace_id(trace_id):
    """Resolve a (possibly abbreviated) trace id; default newest."""
    from skypilot_tpu.observability import tracing
    rows = tracing.list_traces(limit=0)
    if not rows:
        raise click.ClickException(
            "No recorded traces (arm tracing with STPU_TRACE=1).")
    if trace_id is None:
        return rows[-1]["trace_id"]
    matches = [r["trace_id"] for r in rows
               if r["trace_id"].startswith(trace_id)]
    if not matches:
        raise click.ClickException(f"No trace matches {trace_id!r}.")
    if len(matches) > 1:
        raise click.ClickException(
            f"{trace_id!r} is ambiguous ({len(matches)} traces); "
            "give more characters.")
    return matches[0]


@trace.command(name="list")
@click.option("--limit", "-n", type=int, default=20,
              help="Max traces shown (newest last).")
def trace_list(limit):
    """List recorded traces, oldest first."""
    from skypilot_tpu.observability import tracing
    rows = tracing.list_traces(limit=limit)
    if not rows:
        click.echo("No recorded traces (arm tracing with "
                   "STPU_TRACE=1).")
        return
    import time as time_lib
    fmt = "{:<34} {:<20} {:<20} {:>6} {:>10} {:<6}"
    click.echo(fmt.format("TRACE_ID", "ROOT", "STARTED", "SPANS",
                          "DURATION", "STATUS"))
    for r in rows:
        stamp = time_lib.strftime("%Y-%m-%d %H:%M:%S",
                                  time_lib.localtime(r["ts"]))
        click.echo(fmt.format(r["trace_id"], r["name"][:20], stamp,
                              r["spans"], _fmt_dur(r["dur"]),
                              r["status"]))


@trace.command(name="show")
@click.argument("trace_id", required=False)
@click.option("--events", "show_span_events", is_flag=True,
              help="Also print span annotations (retries, breaker "
                   "ejections, policy decisions).")
def trace_show(trace_id, show_span_events):
    """Print one trace as an indented span tree with critical-path
    markers (* = the chain of spans bounding end-to-end latency).
    TRACE_ID may be abbreviated; defaults to the newest trace."""
    from skypilot_tpu.observability import tracing
    tid = _resolve_trace_id(trace_id)
    roots = tracing.assemble(tid)
    if not roots:
        raise click.ClickException(f"Trace {tid} has no spans.")
    n_spans = sum(1 for _ in _walk_spans(roots))
    click.echo(f"trace {tid} ({len(roots)} root(s), {n_spans} spans)")
    for root in roots:
        critical = set(tracing.critical_path(root))
        _print_span_tree(root, "", critical, show_span_events)


def _walk_spans(nodes):
    for node in nodes:
        yield node
        yield from _walk_spans(node["children"])


def _print_span_tree(node, indent, critical, show_span_events):
    span = node["span"]
    mark = " *" if span["span_id"] in critical else ""
    status = span.get("status", "ok")
    extra = "" if status == "ok" else f" [{status}]"
    click.echo(f"{indent}{span.get('name', '?'):<28} "
               f"{_fmt_dur(span.get('dur', 0)):>10}{extra}{mark}")
    if show_span_events:
        for ev in span.get("events") or []:
            detail = " ".join(f"{k}={v}" for k, v in sorted(ev.items())
                              if k not in ("name", "at"))
            click.echo(f"{indent}  · {ev.get('name', '?')} "
                       f"@{_fmt_dur(ev.get('at', 0))} {detail}")
    for child in node["children"]:
        _print_span_tree(child, indent + "  ", critical,
                         show_span_events)


@trace.command(name="export")
@click.argument("trace_id", required=False)
@click.option("--perfetto", is_flag=True, required=True,
              help="Chrome trace-event JSON, loadable in "
                   "ui.perfetto.dev / chrome://tracing.")
@click.option("--output", "-o", default="-",
              help="Output file (default stdout).")
def trace_export(trace_id, perfetto, output):
    """Export one trace (abbreviated TRACE_ID ok; default newest)."""
    del perfetto  # the only format; the flag documents the contract
    from skypilot_tpu.observability import tracing
    import json as json_lib
    tid = _resolve_trace_id(trace_id)
    doc = tracing.to_perfetto(tracing.read(trace_id=tid))
    text = json_lib.dumps(doc, indent=1, default=str)
    if output == "-":
        click.echo(text)
    else:
        with open(output, "w") as f:
            f.write(text)
        click.echo(f"Wrote {len(doc['traceEvents'])} events to "
                   f"{output}.")


@cli.group()
def local():
    """Laptop-local Kubernetes cluster via Kind (reference: `sky local
    up`, sky/cli.py:5054). Tasks target it with `resources: {cloud:
    kubernetes}`."""


@local.command(name="up")
@click.option("--name", default=None,
              help="Kind cluster name (default stpu-local).")
def local_up(name):
    """Create a local Kind cluster for the kubernetes provider."""
    from skypilot_tpu.utils import local_up as local_up_lib
    ctx = local_up_lib.up(name or local_up_lib.DEFAULT_CLUSTER)
    click.echo(f"Local Kubernetes cluster ready (context {ctx}).")
    click.echo("Run tasks against it with:\n"
               "  resources:\n    cloud: kubernetes")


@local.command(name="down")
@click.option("--name", default=None,
              help="Kind cluster name (default stpu-local).")
def local_down(name):
    """Delete the local Kind cluster."""
    from skypilot_tpu.utils import local_up as local_up_lib
    local_up_lib.down(name or local_up_lib.DEFAULT_CLUSTER)
    click.echo("Local Kubernetes cluster deleted.")


@cli.command(name="cost-report")
def cost_report():
    """Accumulated cost per cluster from recorded usage."""
    from skypilot_tpu import core
    fmt = "{:<24} {:<10} {:>10} {:>10}"
    click.echo(fmt.format("NAME", "STATUS", "HOURS", "COST ($)"))
    for r in core.cost_report():
        click.echo(fmt.format(
            r["name"],
            r["status"].value if r["status"] else "-",
            f"{r['duration_seconds'] / 3600:.2f}",
            f"{r['cost']:.2f}"))


@cli.group()
def jobs():
    """Managed jobs: preemption-recovering task execution."""


@jobs.command(name="launch")
@click.argument("entrypoint", required=True)
@click.option("--name", "-n", default=None, help="Managed job name.")
@click.option("--env", multiple=True, help="KEY=VALUE env overrides.")
@click.option("--detach-run", "-d", is_flag=True)
@click.option("--yes", "-y", is_flag=True,
              help="Skip the launch confirmation prompt.")
def jobs_launch(entrypoint, name, env, detach_run, yes):
    """Launch a managed job from a task YAML (single task or multi-doc
    chain pipeline)."""
    from skypilot_tpu import jobs as jobs_sdk
    from skypilot_tpu.jobs import core as jobs_core
    from skypilot_tpu.utils import dag_utils
    try:
        dag = dag_utils.load_chain_dag_from_yaml(
            entrypoint, env_overrides=_parse_env(env))
    except exceptions.SkyTpuError as e:
        raise click.ClickException(str(e)) from e
    if not yes:
        # Managed jobs launch fresh clusters per task (plus recovery
        # relaunches): always show the plan and ask.
        from skypilot_tpu import optimizer as optimizer_lib
        try:
            optimizer_lib.Optimizer.optimize(dag)  # prints the table
        except exceptions.SkyTpuError as e:
            raise click.ClickException(str(e)) from e
        click.confirm(
            f"Launching managed job {name or dag.tasks[0].name!r} "
            f"({len(dag.tasks)} task(s)). Proceed?",
            default=True, abort=True)
    job_id = jobs_sdk.launch(dag, name=name)
    click.echo(f"Managed job {job_id} submitted.")
    if not detach_run:
        sys.exit(jobs_core.tail_logs(job_id, follow=True))


@jobs.command(name="queue")
@click.option("--skip-finished", "-s", is_flag=True)
def jobs_queue(skip_finished):
    """List managed jobs (reference `sky jobs queue` columns).

    CKPT shows resume progress: the newest durable checkpoint step the
    controller observed — what a preemption right now would resume
    from."""
    from skypilot_tpu.jobs import core as jobs_core
    fmt = "{:<5} {:<20} {:<10} {:<18} {:>9} {:>8} {:<24}"
    click.echo(fmt.format("ID", "NAME", "SUBMITTED", "STATUS",
                          "#RECOVER", "CKPT", "CLUSTER"))
    for j in jobs_core.queue(skip_finished=skip_finished):
        step = j.get("last_ckpt_step")
        click.echo(fmt.format(
            j["job_id"], (j["job_name"] or "-")[:20],
            _human_ago(j.get("submitted_at")), j["status"],
            j["recovery_count"],
            "-" if step is None else f"@{step}",
            j["cluster_name"] or "-"))


@jobs.command(name="cancel")
@click.argument("job_ids", nargs=-1, type=int)
@click.option("--all", "-a", "all_jobs", is_flag=True)
def jobs_cancel(job_ids, all_jobs):
    """Cancel managed job(s)."""
    from skypilot_tpu.jobs import core as jobs_core
    done = jobs_core.cancel(list(job_ids) or None, all_jobs=all_jobs)
    click.echo(f"Cancelling managed jobs: {done or 'none'}")


@jobs.command(name="logs")
@click.argument("job_id", required=False, type=int)
@click.option("--no-follow", is_flag=True)
def jobs_logs(job_id, no_follow):
    """Stream a managed job's task logs."""
    from skypilot_tpu.jobs import core as jobs_core
    sys.exit(jobs_core.tail_logs(job_id, follow=not no_follow))


@jobs.command(name="reconcile")
def jobs_reconcile():
    """Adopt orphaned managed jobs (controller process died): resume
    the watch on live clusters, finish interrupted recoveries."""
    from skypilot_tpu.jobs import core as jobs_core
    adopted = jobs_core.reconcile()
    if adopted:
        click.echo(f"Adopting managed jobs: {adopted}")
    else:
        click.echo("No orphaned managed jobs.")


@jobs.command(name="dashboard")
@click.option("--port", default=None, type=int)
@click.option("--host", default=None)
def jobs_dashboard(port, host):
    """Serve an auto-refreshing HTML view of the managed-jobs queue."""
    from skypilot_tpu.jobs import dashboard
    dashboard.run(port or dashboard.DEFAULT_PORT,
                  host or dashboard.DEFAULT_HOST)


def _load_train_doc(job: dict) -> dict:
    """Training telemetry for one managed job: the controller's
    scraped dump (snapshot + time-series), falling back to the raw
    ``snapshot.json`` in the job's trainstats dir when the controller
    has not scraped a tick yet."""
    import json as json_lib
    import os
    from skypilot_tpu.utils import paths
    path = (paths.logs_dir() / "managed_jobs" /
            f"controller-{job['job_id']}-train.json")
    try:
        with open(path) as f:
            doc = json_lib.load(f)
        if isinstance(doc, dict):
            return doc
    except (OSError, ValueError):
        pass
    ckpt_dir = job.get("ckpt_dir")
    if ckpt_dir:
        try:
            with open(os.path.join(ckpt_dir, "trainstats",
                                   "snapshot.json")) as f:
                snap = json_lib.load(f)
            if isinstance(snap, dict):
                return {"snapshot": snap}
        except (OSError, ValueError):
            pass
    return {}


def _render_jobs_top(job: dict, doc: dict) -> str:
    """Human rendering of one job's training telemetry (`stpu jobs
    top`) — mirrors `stpu top`'s layout for the serving fleet."""
    snap = doc.get("snapshot") or {}
    goodput = snap.get("goodput") or {}
    last = snap.get("last") or {}
    # The controller-persisted columns are the fallback when the
    # snapshot is missing (e.g. the task host died mid-write).
    mfu = (snap.get("mfu") if snap.get("mfu") is not None
           else job.get("mfu"))
    tok_s = (snap.get("tokens_per_sec")
             if snap.get("tokens_per_sec") is not None
             else job.get("tok_s"))
    productive = (goodput.get("productive")
                  if goodput.get("productive") is not None
                  else job.get("goodput"))
    ckpt = job.get("last_ckpt_step")
    lines = [
        "job        {} ({})  {}  recoveries {}  ckpt {}".format(
            job["job_id"], job.get("job_name") or "-", job["status"],
            job.get("recovery_count") or 0,
            "-" if ckpt is None else f"@{ckpt}"),
        "train      step/s {}  tok/s {}  MFU {}  at step {}".format(
            _fmt_val(snap.get("steps_per_sec"), "{:.2f}"),
            _fmt_val(tok_s, "{:.0f}"),
            _fmt_val(mfu, "{:.1%}"),
            _fmt_val(last.get("step"), "{:.0f}")),
        "loss       {}  grad_norm {}".format(
            _fmt_val(last.get("loss"), "{:.4f}"),
            _fmt_val(last.get("grad_norm"), "{:.4f}")),
        "goodput    productive {}  data-wait {}  ckpt {}  "
        "restart {}".format(
            _fmt_val(productive, "{:.1%}"),
            _fmt_val(goodput.get("data_wait"), "{:.1%}"),
            _fmt_val(goodput.get("ckpt"), "{:.1%}"),
            _fmt_val(goodput.get("restart"), "{:.1%}")),
        "gang       hosts {}  skew {}s  stragglers {}".format(
            snap.get("hosts") or 1,
            _fmt_val(snap.get("host_skew_s"), "{:.2f}"),
            ",".join(str(h) for h in snap.get("stragglers") or [])
            or "-"),
    ]
    if not snap:
        lines.append("(no trainstats snapshot yet — arm the task "
                     "with STPU_TRAINSTATS=1; docs/observability.md)")
    return "\n".join(lines)


@jobs.command(name="top")
@click.argument("job_id", required=False, type=int)
@click.option("--watch", "-w", is_flag=True,
              help="Refresh until interrupted.")
@click.option("--interval", "-n", type=float, default=2.0,
              show_default=True,
              help="Refresh period for --watch, seconds.")
def jobs_top(job_id, watch, interval):
    """Live training telemetry for a managed job: step/s, tok/s, live
    MFU, the goodput breakdown, gang skew/stragglers, last durable
    checkpoint and recovery count — scraped each watch tick by the
    jobs controller from the task's trainstats snapshot (arm the task
    with STPU_TRAINSTATS=1; see docs/observability.md). Defaults to
    the newest non-terminal job."""
    from skypilot_tpu.jobs import core as jobs_core
    from skypilot_tpu.jobs.state import ManagedJobStatus

    def render_once():
        queue = jobs_core.queue()
        if not queue:
            raise click.ClickException("no managed jobs.")
        if job_id is not None:
            matches = [j for j in queue if j["job_id"] == job_id]
            if not matches:
                raise click.ClickException(
                    f"Managed job {job_id} not found.")
            job = matches[0]
        else:
            live = [j for j in queue
                    if not ManagedJobStatus(j["status"]).is_terminal()]
            job = (live or queue)[0]  # queue is newest-first
        click.echo(_render_jobs_top(job, _load_train_doc(job)))

    _watch_render(render_once, watch, interval)


@cli.group()
def bench():
    """Benchmark a task across candidate TPU types ($/step report)."""


@bench.command(name="launch")
@click.argument("entrypoint", required=True)
@click.option("--benchmark", "-b", required=True, help="Benchmark name.")
@click.option("--candidate", "-c", "candidates", multiple=True,
              required=True,
              help="Accelerator per candidate (repeatable), e.g. "
                   "-c tpu-v5e-8 -c tpu-v5p-8.")
@click.option("--env", multiple=True, help="KEY=VALUE env overrides.")
def bench_launch(entrypoint, benchmark, candidates, env):
    """Launch one cluster per candidate running ENTRYPOINT with step
    callbacks armed."""
    from skypilot_tpu.benchmark import benchmark_utils
    task = _load_task(entrypoint, env, {})
    try:
        res_candidates = [
            task.resources[0].copy(accelerator=acc, instance_type=None)
            for acc in candidates]
        names = benchmark_utils.launch_benchmark(task, res_candidates,
                                                 benchmark)
    except (ValueError, exceptions.SkyTpuError) as e:
        raise click.ClickException(str(e)) from e
    click.echo(f"Benchmark {benchmark}: launched {', '.join(names)}")


@bench.command(name="show")
@click.argument("benchmark", required=True)
def bench_show(benchmark):
    """Refresh and show a benchmark's per-candidate results."""
    from skypilot_tpu.benchmark import benchmark_utils
    rows = benchmark_utils.update_benchmark(benchmark)
    if not rows:
        click.echo(f"No results for benchmark {benchmark!r}.")
        return
    fmt = "{:<26} {:<28} {:<10} {:>7} {:>12} {:>12}"
    click.echo(fmt.format("CLUSTER", "RESOURCES", "STATUS", "STEPS",
                          "SEC/STEP", "$/STEP"))
    for r in rows:
        sps = r.get("seconds_per_step")
        dps = r.get("dollars_per_step")
        click.echo(fmt.format(
            r["cluster_name"], r["resources_str"][:28], r["status"],
            r["num_steps"] if r["num_steps"] is not None else "-",
            f"{sps:.3f}" if sps else "-",
            f"{dps:.6f}" if dps else "-"))


@bench.command(name="down")
@click.argument("benchmark", required=True)
def bench_down(benchmark):
    """Tear down a benchmark's candidate clusters (results kept)."""
    from skypilot_tpu.benchmark import benchmark_utils
    benchmark_utils.update_benchmark(benchmark)
    benchmark_utils.teardown_benchmark(benchmark)
    click.echo(f"Benchmark {benchmark}: clusters torn down.")


@bench.command(name="delete")
@click.argument("benchmark", required=True)
def bench_delete(benchmark):
    """Delete a benchmark's records."""
    from skypilot_tpu.benchmark import benchmark_state
    benchmark_state.delete_benchmark(benchmark)
    click.echo(f"Benchmark {benchmark} deleted.")


@cli.group()
def storage():
    """Storage objects: buckets synced/mounted onto clusters."""


@storage.command(name="ls")
def storage_ls():
    """List registered storage objects."""
    from skypilot_tpu import core
    records = core.storage_ls()
    if not records:
        click.echo("No storage objects.")
        return
    fmt = "{:<28} {:<8} {:<10} {}"
    click.echo(fmt.format("NAME", "STORE", "STATUS", "SOURCE"))
    for r in records:
        handle = r["handle"] or {}
        click.echo(fmt.format(r["name"], handle.get("store", "?"),
                              r["status"] or "?",
                              handle.get("source") or "-"))


@storage.command(name="delete")
@click.argument("names", nargs=-1, required=True)
@click.option("--yes", "-y", is_flag=True, help="Skip confirmation.")
def storage_delete(names, yes):
    """Delete storage object(s): the bucket AND its registry row."""
    from skypilot_tpu import core
    for name in names:
        if not yes:
            click.confirm(f"Delete storage {name!r} (bucket contents "
                          f"included)?", abort=True)
        try:
            core.storage_delete(name)
            click.echo(f"Deleted storage {name}.")
        except exceptions.SkyTpuError as e:
            raise click.ClickException(str(e)) from e


@storage.command(name="transfer")
@click.argument("src", required=True)
@click.argument("dst", required=True)
def storage_transfer(src, dst):
    """Transfer SRC bucket to DST bucket (e.g. s3://b1 gcs://b2).

    s3->gcs runs cloud-side via GCP Storage Transfer Service; gcs->s3
    via gsutil rsync.
    """
    from skypilot_tpu.data import data_transfer

    def parse(uri):
        if "://" not in uri:
            raise click.ClickException(
                f"{uri!r}: want store://bucket (gcs://, s3://, local://)")
        store, bucket = uri.split("://", 1)
        return store.replace("gs", "gcs") if store == "gs" else store, \
            bucket.rstrip("/")

    (src_store, src_bucket), (dst_store, dst_bucket) = parse(src), \
        parse(dst)
    try:
        data_transfer.transfer(src_store, src_bucket, dst_store,
                               dst_bucket)
    except (exceptions.StorageError,
            exceptions.NotSupportedError) as e:
        raise click.ClickException(str(e)) from e
    click.echo(f"Transferred {src} -> {dst}.")


@cli.group()
def serve():
    """Autoscaled serving: one endpoint, N replicas."""


@serve.command(name="up")
@click.argument("entrypoint", required=True)
@click.option("--service-name", "-n", default=None)
@click.option("--env", multiple=True, help="KEY=VALUE env overrides.")
@click.option("--yes", "-y", is_flag=True,
              help="Skip the confirmation prompt.")
def serve_up(entrypoint, service_name, env, yes):
    """Start a service from a task YAML with a `service:` section."""
    from skypilot_tpu.serve import core as serve_core
    task = _load_task(entrypoint, env, {})
    if not yes:
        # Replica-fleet cost preview: the controller launches
        # min_replicas clusters of the replica resources (plus the
        # controller cluster itself in cluster mode).
        from skypilot_tpu import optimizer as optimizer_lib
        spec = task.service
        replicas = getattr(spec, "min_replicas", 1) if spec else 1
        try:
            cands = optimizer_lib.launchable_candidates(task)
        except exceptions.SkyTpuError as e:
            raise click.ClickException(str(e)) from e
        if cands:
            best = min(cands, key=lambda c: c.hourly_price)
            click.echo(
                f"Service replicas: {replicas} x {best.resources!r} @ "
                f"${best.hourly_price:.2f}/hr each "
                f"(~${replicas * best.hourly_price:.2f}/hr total).")
        click.confirm(f"Start service "
                      f"{service_name or task.name or 'service'!r}?",
                      default=True, abort=True)
    name, endpoint = serve_core.up(task, service_name)
    click.echo(f"Service {name} starting; endpoint: {endpoint}")


@serve.command(name="update")
@click.argument("service_name", required=True)
@click.argument("entrypoint", required=True)
@click.option("--env", multiple=True, help="KEY=VALUE env overrides.")
def serve_update(service_name, entrypoint, env):
    """Roll a running service to a new task YAML revision (no downtime:
    new replicas come READY before old ones are drained)."""
    from skypilot_tpu.serve import core as serve_core
    task = _load_task(entrypoint, env, {})
    try:
        version = serve_core.update(task, service_name)
    except exceptions.SkyTpuError as e:
        raise click.ClickException(str(e)) from e
    click.echo(f"Service {service_name} rolling to version {version}.")


@serve.command(name="down")
@click.argument("service_names", nargs=-1)
@click.option("--all", "-a", "all_services", is_flag=True)
def serve_down(service_names, all_services):
    """Tear down service(s)."""
    from skypilot_tpu.serve import core as serve_core
    done = serve_core.down(list(service_names) or None,
                           all_services=all_services)
    click.echo(f"Tearing down: {', '.join(done) or 'none'}")


@serve.command(name="logs")
@click.argument("service_name")
@click.argument("replica_id", type=int, required=False)
@click.option("--no-follow", is_flag=True)
@click.option("--controller", "target", flag_value="controller",
              default=True,
              help="Controller process log (default without "
                   "REPLICA_ID).")
@click.option("--load-balancer", "target", flag_value="load_balancer",
              help="Load balancer process log (its own process; "
                   "survives controller crashes).")
def serve_logs(service_name, replica_id, no_follow, target):
    """Stream service logs: the controller's by default, the LB's with
    --load-balancer, or one replica's job logs when REPLICA_ID is given
    (reference: sky serve logs --controller/--load-balancer)."""
    if replica_id is not None and target == "load_balancer":
        raise click.UsageError(
            "REPLICA_ID and --load-balancer are mutually exclusive.")
    from skypilot_tpu.serve import core as serve_core
    sys.exit(serve_core.logs(service_name, replica_id,
                             follow=not no_follow, target=target))


@serve.command(name="status")
@click.argument("service_names", nargs=-1)
def serve_status(service_names):
    """Show services and their replicas."""
    from skypilot_tpu.serve import core as serve_core
    fmt = "{:<20} {:<16} {:<24} {:<8}"
    click.echo(fmt.format("SERVICE", "STATUS", "ENDPOINT", "#READY"))
    # serve_core.status() normalizes statuses to plain strings.
    for svc in serve_core.status(list(service_names) or None):
        n_ready = sum(1 for r in svc["replicas"]
                      if r["status"] == "READY")
        status_text = svc["status"]
        if svc.get("degraded"):
            # SLO burn-rate monitor flagged a live breach: the service
            # still serves (status READY) but is DEGRADED — surface it
            # on the line operators actually look at.
            status_text += " [DEGRADED]"
        click.echo(fmt.format(svc["service_name"], status_text,
                              svc["endpoint"], n_ready))
        for r in svc["replicas"]:
            kind = "[spot]" if r.get("is_spot") else ""
            click.echo(f"  replica {r['replica_id']:<3} "
                       f"{r['status']:<14} {r['url'] or '-'} {kind}")
        scale = svc.get("last_scale_event")
        if scale:
            click.echo(
                f"  last scale action: {scale.get('event')} "
                f"{scale.get('previous')}->{scale.get('target')} "
                f"replicas at {scale.get('qps')} qps "
                f"({_human_ago(scale.get('ts'))})")
        slo_ev = svc.get("slo_event")
        if svc.get("degraded") and slo_ev:
            click.echo(
                f"  slo breach: {slo_ev.get('objective')} objective, "
                f"burn fast {slo_ev.get('burn_fast')} / slow "
                f"{slo_ev.get('burn_slow')} "
                f"({_human_ago(slo_ev.get('ts'))}) — see `stpu slo`")


def main():
    cli()


if __name__ == "__main__":
    main()
