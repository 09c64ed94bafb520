"""Daemon entrypoint: run a mon or OSD as its own OS process over TCP.

The ceph-mon/ceph-osd analogue (ref: src/ceph_mon.cc, src/ceph_osd.cc
global_init + daemon loop): a monmap JSON file carries every entity's
bind address plus the cluster bootstrap parameters; each process binds
its own socket and joins.

monmap JSON:
    {"addrs": {"mon.0": ["127.0.0.1", 6789], "osd.0": [...], ...},
     "mon_ranks": [0], "n_osd": 3, "osds_per_host": 1}

Usage:
    python -m ceph_tpu.tools.daemon_main mon --rank 0 --monmap m.json
    python -m ceph_tpu.tools.daemon_main osd --id 2 --monmap m.json
"""
from __future__ import annotations

import argparse
import json
import signal
import sys
import time


def load_monmap(path: str) -> dict:
    with open(path) as f:
        mm = json.load(f)
    mm["addrs"] = {k: tuple(v) for k, v in mm["addrs"].items()}
    return mm



def make_net(mm: dict, keyring) -> "TcpNet":
    """TcpNet for this monmap; `"ms_secure_mode": true` in the monmap
    switches every frame to sealed secure mode keyed by the keyring's
    service secret (ref: msgr v2 secure mode; requires --keyring)."""
    from ..msg.tcp import TcpNet
    secret = None
    if mm.get("ms_secure_mode"):
        if keyring is None:
            raise SystemExit("ms_secure_mode requires --keyring")
        from ..auth import SERVICE_ENTITY
        secret = keyring.get(SERVICE_ENTITY)
        if secret is None:
            # failing open to plaintext here would silently void the
            # operator's secure-mode intent
            raise SystemExit(
                "ms_secure_mode: keyring has no service secret")
    return TcpNet(mm["addrs"], secure_secret=secret,
                  compress=mm.get("ms_compress"))

def _crash_dir(args) -> str | None:
    """Spool dir for crash reports: --crash-dir, else <data-dir>/crash
    (the /var/lib/ceph/crash layout), else none (post-only)."""
    if getattr(args, "crash_dir", ""):
        return args.crash_dir
    if getattr(args, "data_dir", ""):
        import os
        return os.path.join(args.data_dir, "crash")
    return None


def run_mon(args) -> int:
    from ..mon.monitor import Monitor, build_initial
    from ..msg.tcp import TcpNet
    mm = load_monmap(args.monmap)
    m, w = build_initial(mm.get("n_osd", 0),
                         osds_per_host=mm.get("osds_per_host", 1))
    ranks = mm.get("mon_ranks", [0])
    keyring = None
    if args.keyring:
        from ..auth import KeyRing
        keyring = KeyRing.load(args.keyring)
    net = make_net(mm, keyring)
    store = None
    if args.data_dir:
        # durable mon store on the KV engine (ref: MonitorDBStore on
        # RocksDB): a restarted mon resumes from committed paxos state
        from ..kv import LogDB
        from ..mon.store import MonitorStore
        store = MonitorStore(LogDB(args.data_dir))
    mon = Monitor(net, rank=args.rank, initial_map=m, initial_wrapper=w,
                  store=store,
                  mon_ranks=ranks if len(ranks) > 1 else None,
                  keyring=keyring, crash_dir=_crash_dir(args))
    mon.crash_reporter.install_excepthook()
    mon.init()
    if args.asok:
        mon.start_admin_socket(args.asok)
    print(f"mon.{args.rank}: serving on "
          f"{mm['addrs'][f'mon.{args.rank}']}", flush=True)
    _serve(lambda: mon.tick(), interval=1.0)
    mon.shutdown()
    return 0


def run_osd(args) -> int:
    from ..common.options import global_config
    from ..msg.tcp import TcpNet
    from ..osd.daemon import OSDDaemon
    mm = load_monmap(args.monmap)
    mons = [f"mon.{r}" for r in mm.get("mon_ranks", [0])]
    store = None
    if args.data_dir:
        if getattr(args, "objectstore", "bluestore") == "journaled":
            from ..store import JournaledStore
            store = JournaledStore(args.data_dir)
        else:
            # the durable default (ref: bluestore as the OSD default;
            # JournaledStore retired to an opt-in legacy engine)
            from ..store import BlueStore
            store = BlueStore(args.data_dir)
            store.mkfs()
        store.mount()
    keyring = None
    if args.keyring:
        from ..auth import KeyRing
        keyring = KeyRing.load(args.keyring)
    net = make_net(mm, keyring)
    d = OSDDaemon(net, args.id, mon=mons, store=store, keyring=keyring,
                  crash_dir=_crash_dir(args))
    d.crash.install_excepthook()
    d.init()
    if args.asok:
        d.start_admin_socket(args.asok)
    print(f"osd.{args.id}: serving on "
          f"{mm['addrs'][f'osd.{args.id}']}", flush=True)
    interval = global_config()["osd_heartbeat_interval"]
    _serve(lambda: d.heartbeat_tick(), interval=interval)
    d.shutdown()
    if store is not None:
        store.umount()
    return 0


def run_mds(args) -> int:
    """(ref: src/ceph_mds.cc)."""
    import os
    from ..client import Rados
    from ..fs.mds import MDSDaemon
    from ..msg.tcp import TcpNet
    mm = load_monmap(args.monmap)
    keyring = None
    if getattr(args, "keyring", ""):
        from ..auth import KeyRing
        keyring = KeyRing.load(args.keyring)
    net = make_net(mm, keyring)
    r = Rados(make_net(mm, keyring),
              name=f"client.mds{os.getpid() % 10000}")
    if keyring is not None:
        # the MDS's embedded RADOS client signs as the daemon itself:
        # it holds the service secret, so it self-mints (a wire
        # handshake would fail — the mon has no key for the ephemeral
        # client name)
        from ..auth import attach_cephx
        attach_cephx(r.objecter.ms, f"mds.{args.rank}", keyring,
                     verifier=False)
    r.connect()
    mds = MDSDaemon(net, r, rank=args.rank, keyring=keyring,
                    crash_dir=_crash_dir(args))
    # crash posts go to the mons even though this MDS runs standalone
    # (no beacons/fsmap — crash_mons is independent of `mon=`)
    mds.crash_mons = [f"mon.{k}" for k in mm.get("mon_ranks", [0])]
    rep = mds.crash_reporter
    rep.install_excepthook()
    mds.init()
    # next-boot spool drain: crashes captured while the mons were
    # unreachable post now (the table dedups by crash_id; the ack
    # retires each spool copy)
    rep.drain()
    print(f"mds.{args.rank}: serving on "
          f"{mm['addrs'][f'mds.{args.rank}']}", flush=True)

    def _tick():
        # the tick drives the load balancer (heat decay, load
        # publication, hot-subtree export); crash-capture wraps it
        # like the osd/mon tick entries
        try:
            mds.tick()
        except Exception as exc:
            rep.capture(exc)
            raise
    _serve(_tick, interval=1.0)
    mds.shutdown()
    r.shutdown()
    return 0


def _serve(tick, interval: float) -> None:
    stop = {"flag": False}

    def on_sig(_sig, _frm):
        stop["flag"] = True

    signal.signal(signal.SIGTERM, on_sig)
    signal.signal(signal.SIGINT, on_sig)
    while not stop["flag"]:
        time.sleep(interval)
        try:
            tick()
        except Exception as ex:           # daemon loop must survive
            print(f"tick error: {ex}", file=sys.stderr, flush=True)


def main(argv=None) -> int:
    # the env layer propagates CEPH_TPU_ERRCHECK from the parent
    # (tests/conftest.py or the errcov smoke) — arm the error-path
    # coverage hook FIRST so run_mon/run_osd's daemon imports are
    # instrumented; with CEPH_TPU_ERRCHECK_DIR set this process dumps
    # its handler counters there at exit for the parent to merge
    from ..common import errcheck
    errcheck.enable_if_configured()
    # ... CEPH_TPU_JAXGUARD the same way, same as lockdep —
    # arm BEFORE daemon imports build any jit wrapper
    from ..common import jaxguard
    jaxguard.enable_if_configured()
    # ... and CEPH_TPU_RACECHECK the same way, so TCP multi-process
    # daemons run the lockset sanitizer their parent suite runs
    from ..common import racecheck
    racecheck.enable_if_configured()
    ap = argparse.ArgumentParser(prog="ceph-tpu-daemon")
    sub = ap.add_subparsers(dest="role", required=True)
    pm = sub.add_parser("mon")
    pm.add_argument("--rank", type=int, default=0)
    pm.add_argument("--monmap", required=True)
    pm.add_argument("--data-dir", default="",
                    help="durable mon store directory (KV-backed); "
                         "in-memory when omitted")
    pm.add_argument("--asok", default="",
                    help="admin socket path (`ceph daemon` endpoint)")
    pm.add_argument("--keyring", default="",
                    help="cephx keyring JSON (enables auth)")
    pm.add_argument("--crash-dir", default="",
                    help="crash-report spool dir (default: "
                         "<data-dir>/crash when --data-dir is set)")
    po = sub.add_parser("osd")
    po.add_argument("--id", type=int, required=True)
    po.add_argument("--monmap", required=True)
    po.add_argument("--data-dir", default="",
                    help="durable store directory (BlueStore); "
                         "in-memory when omitted")
    po.add_argument("--objectstore", default="bluestore",
                    choices=["bluestore", "journaled"],
                    help="durable engine (journaled = legacy)")
    po.add_argument("--asok", default="",
                    help="admin socket path (`ceph daemon` endpoint)")
    po.add_argument("--keyring", default="",
                    help="cephx keyring JSON (enables auth)")
    po.add_argument("--crash-dir", default="",
                    help="crash-report spool dir (default: "
                         "<data-dir>/crash when --data-dir is set)")
    pd = sub.add_parser("mds")
    pd.add_argument("--rank", type=int, default=0)
    pd.add_argument("--monmap", required=True)
    pd.add_argument("--keyring", default="",
                    help="cephx keyring JSON (auth/secure clusters)")
    pd.add_argument("--crash-dir", default="",
                    help="crash-report spool dir")
    args = ap.parse_args(argv)
    if args.role != "osd":
        # only OSDs run device code, and a chip belongs to one
        # process: a mon or mds must never take the host's TPU
        import jax
        jax.config.update("jax_platforms", "cpu")
    return {"mon": run_mon, "osd": run_osd,
            "mds": run_mds}[args.role](args)


if __name__ == "__main__":
    sys.exit(main())
