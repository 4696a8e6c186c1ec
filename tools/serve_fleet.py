#!/usr/bin/env python
"""Launch a serving fleet: router + N local ``serve_lm`` replicas.

The process tree mirrors the paper's chief/worker cluster on one
machine: this process is the coordination-only router (no model, no
accelerator) and each replica is a full ``tools/serve_lm.py`` serving
stack on an OS-assigned port. Replica flags are whatever this launcher
doesn't recognise — they are forwarded verbatim, so every ``serve_lm``
knob works per-fleet:

  python tools/serve_fleet.py --num_replicas 2 --router_port 8100 \\
      --demo --slots 4 --d_model 128 --num_layers 4

  curl -s localhost:8100/generate -d '{"prompt": [7,8,9]}'
  curl -s localhost:8100/fleet.json   # per-replica states + pressure
  curl -s localhost:8100/metrics      # fleet gauges, Prometheus text
  curl -s localhost:8100/healthz      # 200 iff >= 1 replica is up

Replicas bind port 0 and announce their address on stdout (the
``serving on http://…`` line ``serve_lm`` already prints); the launcher
parses that, so N replicas never race for ports. SIGTERM/SIGINT to the
launcher drains the whole fleet: replicas get SIGTERM (their own drain
path finishes accepted work), then the router exits.

Hot deploy composes through the same forwarding: pass the
``DeployConfig`` flags (``--watch_dir``, ``--canary_percent``,
``--deploy_variant``, …) and every replica runs its own checkpoint
watcher against the shared directory — a committed save rolls across
the fleet one canaried swap at a time, replicas advertise their live
weight version + variant table on ``/healthz``, and the router routes
variant-pinned traffic (explicit ``"variant"`` in the body, or the
fleet canary resolve on ``client_id``) to replicas that carry it.

``launch_fleet()`` / ``ReplicaProc`` are importable — ``bench.py`` and
the e2e kill-a-replica test drive the same spawning code as the CLI.

Elastic mode (``--supervise``): instead of a static launch list, the
:class:`serve.fleet.elastic.FleetSupervisor` owns every replica process
— it replaces dead replicas, scales between ``--min_replicas`` and
``--max_replicas`` on sustained ``fleet_pressure`` / SLO breaches, and
drains (never SIGKILLs in-flight work) on scale-down. Every replica the
supervisor brings up — including replacements, long after startup — is
re-announced on THIS process's stdout with the same ``serving on
http://… pid=… role=…`` prefix, so external discovery keeps working.

Disaggregated tiers (``--prefill_replicas N --decode_replicas M``):
replicas boot role-tagged; the router steers fresh prompts at the
prefill tier, which runs prefill + first token and then hands each
slot's KV pages to a decode replica (``POST /handoff``). The launcher
(and the supervisor, on every membership change) pushes the decode
tier's URLs to each prefill replica via ``POST /admin/handoff_peers``.
"""

from __future__ import annotations

import argparse
import collections
import os
import signal
import subprocess
import sys
import threading
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

_TOOLS_DIR = os.path.dirname(os.path.abspath(__file__))
_URL_PREFIX = "serving on "


class ReplicaProc:
    """One spawned ``serve_lm`` replica: process handle, parsed URL, and
    a bounded tail of its output (kept readable after startup so the
    child never blocks on a full stdout pipe)."""

    def __init__(self, proc: subprocess.Popen):
        self.proc = proc
        self.url: str | None = None
        self.role: str = "mixed"
        self.tail = collections.deque(maxlen=200)
        self._url_ready = threading.Event()
        self._reader = threading.Thread(target=self._read, daemon=True)
        self._reader.start()

    def _read(self):
        for line in self.proc.stdout:
            line = line.rstrip("\n")
            self.tail.append(line)
            if self.url is None and line.startswith(_URL_PREFIX):
                self.url = line[len(_URL_PREFIX):].split()[0]
                self._url_ready.set()
        self._url_ready.set()  # EOF: unblock waiters even on crash

    def wait_url(self, timeout_s: float) -> str:
        if not self._url_ready.wait(timeout_s) or self.url is None:
            raise RuntimeError(
                f"replica pid {self.proc.pid} did not announce a URL "
                f"within {timeout_s}s; output tail:\n"
                + "\n".join(self.tail)
            )
        return self.url

    def alive(self) -> bool:
        return self.proc.poll() is None

    def terminate(self, grace_s: float = 15.0) -> None:
        if self.proc.poll() is None:
            self.proc.terminate()  # SIGTERM -> serve_lm drain path
            try:
                self.proc.wait(grace_s)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait(5.0)


_CHIP_PROBE = (
    "import jax; d = jax.devices(); print('CHIPS', d[0].platform, len(d))"
)


_SPAWNING = object()  # a chip claimed by a spawn() whose child is starting


class ChipPool:
    """One TPU chip per replica process. A chip belongs to one process at
    a time, and a ``serve_lm`` child left alone opens every chip of the
    host — so the second replica dies at backend init ("The TPU is
    already in use by process with pid N"). The launcher therefore
    counts the chips WITHOUT touching JAX itself (a probe child, reaped
    before any replica starts) and hands each replica its own chip through
    libtpu's visibility variables. ``chips == 0`` (children held to
    another platform, or no TPU) assigns nothing."""

    def __init__(self, env=None):
        self.env = dict(os.environ if env is None else env)
        self.chips = self._count(self.env)
        self._owners: list = [None] * self.chips
        self._lock = threading.Lock()

    @staticmethod
    def _count(env) -> int:
        platforms = env.get("JAX_PLATFORMS", "")
        if platforms and "tpu" not in platforms.split(","):
            return 0  # children cannot land on a chip; skip the probe
        out = subprocess.run(
            [sys.executable, "-c", _CHIP_PROBE], env=env,
            capture_output=True, text=True, timeout=180,
        )
        if out.returncode != 0:
            raise RuntimeError(
                f"chip probe exited {out.returncode}:\n{out.stderr[-2000:]}")
        platform, count = out.stdout.rsplit("CHIPS", 1)[-1].split()[:2]
        return int(count) if platform == "tpu" else 0

    def require(self, replicas: int, replica_argv) -> None:
        """Fail BEFORE spawning when the launch cannot fit the host."""
        if not self.chips:
            return
        tp_parser = argparse.ArgumentParser(add_help=False)
        tp_parser.add_argument("--tp", type=int, default=1)
        tp = tp_parser.parse_known_args(list(replica_argv))[0].tp
        if tp > 1:
            raise ValueError(
                f"--tp {tp}: this launcher gives each replica ONE chip; "
                "run a tp-wide replica with tools/serve_lm.py directly")
        if replicas > self.chips:
            raise ValueError(
                f"{replicas} replicas need {replicas} chips but this host "
                f"has {self.chips} — one process per chip")

    def spawn(self, cmd) -> subprocess.Popen:
        env, chip = self.env, None
        if self.chips:
            with self._lock:
                free = [i for i, p in enumerate(self._owners)
                        if p is None
                        or (p is not _SPAWNING and p.poll() is not None)]
                if not free:
                    raise RuntimeError(
                        f"all {self.chips} chips are held by live replicas")
                chip = free[0]
                self._owners[chip] = _SPAWNING
            # The smallest set libtpu 0.0.34 honours (checked on the
            # four-chip v5e host, PR 21): TPU_VISIBLE_CHIPS alone still
            # trips "The TPU is already in use"; the two bounds variables
            # make each child a one-chip process of its own.
            env = dict(
                env,
                TPU_VISIBLE_CHIPS=str(chip),
                TPU_CHIPS_PER_PROCESS_BOUNDS="1,1,1",
                TPU_PROCESS_BOUNDS="1,1,1",
            )
        proc = None
        try:
            proc = subprocess.Popen(
                cmd, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                text=True, env=env,
            )
        finally:
            if chip is not None:
                with self._lock:
                    self._owners[chip] = proc  # None again if Popen raised
        return proc


def launch_fleet(
    num_replicas: int,
    replica_argv,
    *,
    env=None,
    startup_timeout_s: float = 180.0,
) -> list[ReplicaProc]:
    """Spawn N replicas (port 0 each, one chip each on a TPU host) and
    wait for every URL. Spawning is eager and waiting sequential, so the
    expensive part — jax import + engine warmup — overlaps across
    replicas. On any failure the already-started replicas are torn down
    before the raise."""
    pool = ChipPool(env)
    pool.require(num_replicas, replica_argv)
    replicas = []
    try:
        for _ in range(num_replicas):
            cmd = [
                sys.executable, os.path.join(_TOOLS_DIR, "serve_lm.py"),
                "--port", "0", *replica_argv,
            ]
            replicas.append(ReplicaProc(pool.spawn(cmd)))
        deadline = time.monotonic() + startup_timeout_s
        for replica in replicas:
            replica.wait_url(max(1.0, deadline - time.monotonic()))
        return replicas
    except Exception:
        for replica in replicas:
            replica.terminate(grace_s=2.0)
        raise


def decode_peer_infos(registry, decode_urls) -> list:
    """Enrich decode-tier URLs with the registry's latest probe pressure
    (pages_free/pages_total, queue depth, occupancy) so prefill outboxes
    can score peers instead of round-robining. URLs the registry has not
    probed yet stay bare strings — the outbox falls back to RR for
    them."""
    by_url = {}
    try:
        for rep in registry.snapshot()["replicas"].values():
            by_url[rep["base_url"].rstrip("/")] = rep
    except Exception:  # noqa: BLE001 — enrichment is best-effort
        return list(decode_urls)
    out = []
    for url in decode_urls:
        rep = by_url.get(str(url).rstrip("/"))
        if rep is None:
            out.append(url)
            continue
        out.append({
            "url": url,
            "pages_free": rep.get("pages_free", 0),
            "pages_total": rep.get("pages_total", 0),
            "queue_depth": rep.get("queue_depth", 0),
            "occupancy": rep.get("occupancy", 0.0),
        })
    return out


def push_handoff_peers(prefill_urls, decode_urls,
                       timeout_s: float = 5.0) -> None:
    """POST the decode tier's membership to every prefill replica's
    handoff outbox. Entries are bare URLs or ``decode_peer_infos``
    pressure dicts. Best-effort: a replica that is mid-boot or gone gets
    the next membership push."""
    import json
    import urllib.request

    body = json.dumps({"urls": list(decode_urls)}).encode()
    for url in prefill_urls:
        try:
            req = urllib.request.Request(
                url.rstrip("/") + "/admin/handoff_peers", data=body,
                headers={"Content-Type": "application/json"},
                method="POST")
            urllib.request.urlopen(req, timeout=timeout_s).read()
        except Exception:  # noqa: BLE001 — membership pushes are repeated
            pass


def main(argv=None):
    from distributed_tensorflow_tpu import obs
    from distributed_tensorflow_tpu.config import (
        FleetConfig,
        add_dataclass_flags,
        from_args,
    )
    from distributed_tensorflow_tpu.serve.fleet import (
        FleetRouter,
        FleetSupervisor,
        ReplicaRegistry,
        make_router_server,
    )

    parser = argparse.ArgumentParser()
    add_dataclass_flags(parser, FleetConfig)
    ns, replica_argv = parser.parse_known_args(argv)
    fleet_cfg = from_args(FleetConfig, ns)
    if fleet_cfg.num_replicas < 1:
        sys.exit("--num_replicas must be >= 1")
    tiered = fleet_cfg.prefill_replicas > 0 or fleet_cfg.decode_replicas > 0
    if tiered and (fleet_cfg.prefill_replicas < 1
                   or fleet_cfg.decode_replicas < 1):
        sys.exit("a disaggregated fleet needs --prefill_replicas >= 1 "
                 "AND --decode_replicas >= 1")

    if tiered:
        initial_roles = (["prefill"] * fleet_cfg.prefill_replicas
                         + ["decode"] * fleet_cfg.decode_replicas)
    else:
        initial_roles = ["mixed"] * fleet_cfg.num_replicas

    pool = ChipPool()
    try:
        pool.require(
            max(len(initial_roles),
                fleet_cfg.max_replicas if fleet_cfg.supervise else 0),
            replica_argv)
    except ValueError as err:
        sys.exit(f"serve_fleet: {err}")
    if pool.chips:
        print(f"serve_fleet: {pool.chips} TPU chips, one per replica",
              flush=True)

    def spawn_replica(role: str) -> ReplicaProc:
        """Spawn one role-tagged replica and wait for its URL; every
        (re)announcement reuses serve_lm's ``serving on`` prefix so
        discovery that tails THIS process keeps working in supervised
        mode, where replacements appear long after startup."""
        extra = [] if role == "mixed" else ["--role", role]
        cmd = [
            sys.executable, os.path.join(_TOOLS_DIR, "serve_lm.py"),
            "--port", "0", *extra, *replica_argv,
        ]
        proc = pool.spawn(cmd)
        replica = ReplicaProc(proc)
        url = replica.wait_url(180.0)
        replica.role = role
        print(f"serving on {url} pid={proc.pid} role={role}", flush=True)
        return replica

    if fleet_cfg.router_obs_dir:
        # Router-side dump dir: breaker-open flight-recorder dumps and
        # the end-of-run storm summary. Deliberately NOT --obs_dir (that
        # flag is forwarded verbatim to every replica).
        obs.set_dump_dir(fleet_cfg.router_obs_dir)

    registry = ReplicaRegistry(
        [],
        up_after=fleet_cfg.up_after,
        down_after=fleet_cfg.down_after,
        breaker_window=fleet_cfg.breaker_window,
        breaker_fail_threshold=fleet_cfg.breaker_fail_threshold,
        breaker_min_samples=fleet_cfg.breaker_min_samples,
        breaker_open_s=fleet_cfg.breaker_open_s,
    )
    supervisor = None
    replicas: list[ReplicaProc] = []

    def on_membership(members) -> None:
        """Supervised membership changed: keep every prefill replica's
        decode-peer list current."""
        if not tiered:
            return
        decode_urls = [m.handle.url for m in members
                       if m.role == "decode" and not m.draining]
        prefill_urls = [m.handle.url for m in members
                        if m.role == "prefill" and not m.draining]
        push_handoff_peers(prefill_urls,
                           decode_peer_infos(registry, decode_urls))

    if fleet_cfg.supervise:
        print(
            f"serve_fleet: supervising {len(initial_roles)} replicas "
            f"(min={fleet_cfg.min_replicas} max={fleet_cfg.max_replicas} "
            f"watermarks={fleet_cfg.scale_low_watermark}/"
            f"{fleet_cfg.scale_high_watermark} "
            f"{' '.join(replica_argv) or 'default flags'})",
            flush=True,
        )
        supervisor = FleetSupervisor(
            registry,
            spawn_replica,
            min_replicas=fleet_cfg.min_replicas,
            max_replicas=fleet_cfg.max_replicas,
            high_watermark=fleet_cfg.scale_high_watermark,
            low_watermark=fleet_cfg.scale_low_watermark,
            scale_up_sustain_s=fleet_cfg.scale_up_sustain_s,
            scale_down_sustain_s=fleet_cfg.scale_down_sustain_s,
            cooldown_s=fleet_cfg.scale_cooldown_s,
            drain_grace_s=fleet_cfg.drain_grace_s,
            # Elastic capacity lands in the decode tier (prefill work is
            # bursty but short; decode holds slots for whole responses).
            role_for=(lambda direction: "decode") if tiered
            else (lambda direction: "mixed"),
            balance_tiers=bool(getattr(fleet_cfg, "balance_tiers", False)
                               and tiered),
            on_change=on_membership,
        )
        supervisor.start(len(initial_roles), roles=initial_roles,
                         interval_s=fleet_cfg.supervisor_tick_s)
        expected_up = supervisor.member_count()
    else:
        print(
            f"serve_fleet: starting {len(initial_roles)} replicas "
            f"({' '.join(replica_argv) or 'default flags'})",
            flush=True,
        )
        replicas = [spawn_replica(role) for role in initial_roles]
        for replica in replicas:
            registry.add(replica.url)
        if tiered:
            push_handoff_peers(
                [r.url for r in replicas if r.role == "prefill"],
                [r.url for r in replicas if r.role == "decode"],
            )
        expected_up = len(replicas)

    router = FleetRouter(
        registry,
        max_attempts=fleet_cfg.max_attempts,
        read_timeout_s=fleet_cfg.read_timeout_s,
        hedge_after_s=(None if fleet_cfg.hedge_after_s < 0
                       else fleet_cfg.hedge_after_s),
    )
    slo_rules = obs.parse_slo_flag(
        fleet_cfg.fleet_slo, defaults=obs.default_fleet_rules)
    slo_monitor = (obs.SloMonitor(registry.metrics_registry, slo_rules)
                   if slo_rules else None)
    if slo_monitor is not None and supervisor is not None:
        supervisor.attach_slo(slo_monitor)
    server = make_router_server(
        router, fleet_cfg.router_host, fleet_cfg.router_port,
        slo=slo_monitor)
    registry.start(fleet_cfg.probe_interval_s)
    # Let the hysteresis see enough probes to mark replicas up before we
    # announce — the URLs were parsed from live servers, so this is quick.
    deadline = time.monotonic() + 30.0
    while registry.up_count() < expected_up and time.monotonic() < deadline:
        time.sleep(fleet_cfg.probe_interval_s)
    if slo_monitor is not None:
        slo_monitor.start(fleet_cfg.fleet_slo_interval_s)
    host, port = server.server_address
    member_urls = ([m.handle.url for m in supervisor.members]
                   if supervisor is not None
                   else [r.url or "?" for r in replicas])
    print(
        f"router on http://{host}:{port}  replicas="
        f"{','.join(member_urls)} "
        f"up={registry.up_count()}",
        flush=True,
    )

    def _on_signal(signum, frame):
        threading.Thread(target=server.shutdown, daemon=True).start()

    signal.signal(signal.SIGTERM, _on_signal)
    signal.signal(signal.SIGINT, _on_signal)

    pressure_stop = threading.Event()
    if tiered:
        # Peer-pressure refresh: membership pushes happen on change, but
        # the PRESSURE attached to each decode peer (pages_free, queue
        # depth) goes stale between changes — re-push the enriched list
        # on a probe-paced cadence so prefill outboxes keep steering at
        # current capacity, not boot-time capacity.
        def repush_pressure() -> None:
            interval = max(0.5, fleet_cfg.probe_interval_s * 4)
            while not pressure_stop.wait(interval):
                try:
                    if supervisor is not None:
                        members = supervisor.members
                        decode_urls = [m.handle.url for m in members
                                       if m.role == "decode"
                                       and not m.draining]
                        prefill_urls = [m.handle.url for m in members
                                        if m.role == "prefill"
                                        and not m.draining]
                    else:
                        decode_urls = [r.url for r in replicas
                                       if r.role == "decode"]
                        prefill_urls = [r.url for r in replicas
                                        if r.role == "prefill"]
                    push_handoff_peers(
                        prefill_urls,
                        decode_peer_infos(registry, decode_urls))
                except Exception:  # noqa: BLE001 — refresh is best-effort
                    pass

        threading.Thread(target=repush_pressure, name="handoff-pressure",
                         daemon=True).start()
    def write_storm_summary() -> None:
        """Fleet-wide chaos/storm summary: final breaker states, every
        ``fleet_*`` counter/gauge, and the per-replica snapshot — the
        one file an operator (or the chaos gate) reads after a storm."""
        if not fleet_cfg.router_obs_dir:
            return
        import json
        try:
            metrics = {}
            for fam in registry.metrics_registry.collect():
                if not fam.name.startswith("fleet_"):
                    continue
                if fam.kind == "histogram":
                    continue
                for label_values, inst in fam.children():
                    key = fam.name
                    if label_values:
                        key += "{" + ",".join(label_values) + "}"
                    metrics[key] = inst.value
            summary = {
                "t_wall": time.time(),
                "breakers_closed": registry.breakers_closed(),
                "replicas": registry.snapshot(),
                "fleet_metrics": metrics,
            }
            os.makedirs(fleet_cfg.router_obs_dir, exist_ok=True)
            path = os.path.join(fleet_cfg.router_obs_dir,
                                "fleet_storm_summary.json")
            with open(path, "w") as f:
                json.dump(summary, f, indent=2, default=str)
        except Exception:  # noqa: BLE001 — summary is best-effort
            pass

    try:
        server.serve_forever()
    finally:
        server.server_close()
        pressure_stop.set()
        if slo_monitor is not None:
            slo_monitor.stop()
        write_storm_summary()
        registry.stop()
        if supervisor is not None:
            supervisor.stop(drain=True)
        for replica in replicas:
            replica.terminate()
        print("serve_fleet: shut down cleanly", flush=True)


if __name__ == "__main__":
    main()
