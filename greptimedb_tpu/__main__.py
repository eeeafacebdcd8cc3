"""CLI: `python -m greptimedb_tpu <subcommand>`.

Role-equivalent of the reference's `greptime` binary (reference
cmd/src/bin/greptime.rs:26-61): `standalone start` brings up the all-in-one
server; `sql` executes statements against a data dir; `export`/`import`
move table data as Parquet (reference cli data export/import).
"""

from __future__ import annotations

import argparse
import json
import sys


def cmd_standalone(args):
    from .database import Database
    from .servers.http import HttpServer
    from .servers.mysql import MysqlServer
    from .servers.postgres import PostgresServer
    from .utils.config import Config

    cfg = Config.load(args.config)
    if args.data_home:
        cfg.storage.data_home = args.data_home
        cfg.storage.wal_dir = ""
        cfg.storage.sst_dir = ""
        cfg.storage.__post_init__()
    if args.http_addr:
        cfg.server.http_addr = args.http_addr
    if args.mysql_addr:
        cfg.server.mysql_addr = args.mysql_addr
    if args.postgres_addr:
        cfg.server.postgres_addr = args.postgres_addr
    db = Database(config=cfg)
    srv = HttpServer(db, cfg.server.http_addr).start()
    mysql = MysqlServer(db, cfg.server.mysql_addr).start(warm=False)
    pg = PostgresServer(db, cfg.server.postgres_addr).start(warm=False)
    print(f"greptimedb-tpu standalone listening on http://{srv.address}", flush=True)
    print(f"mysql on {mysql.address}, postgres on {pg.address}", flush=True)
    print(f"data home: {cfg.storage.data_home}", flush=True)
    try:
        import signal
        import threading

        stop = threading.Event()
        signal.signal(signal.SIGINT, lambda *_: stop.set())
        signal.signal(signal.SIGTERM, lambda *_: stop.set())
        stop.wait()
    finally:
        pg.stop()
        mysql.stop()
        srv.stop()
        db.close()
    return 0


def cmd_sql(args):
    from .database import Database

    db = Database(data_home=args.data_home)
    try:
        text = args.query or sys.stdin.read()
        for result in db.sql(text):
            if result is None:
                print("OK")
            elif isinstance(result, int):
                print(f"{result} rows affected")
            else:
                print(result.to_pandas().to_string(index=False) if args.pretty else result)
    finally:
        db.close()
    return 0


def cmd_export(args):
    import pyarrow.parquet as pq

    from .database import Database
    from .query.logical_plan import TableScan

    db = Database(data_home=args.data_home)
    try:
        meta = db.catalog.table(args.table)
        table = db._scan(TableScan(args.table, meta.database))
        pq.write_table(table, args.output)
        print(f"exported {table.num_rows} rows to {args.output}")
    finally:
        db.close()
    return 0


def cmd_import(args):
    import pyarrow.parquet as pq

    from .database import Database

    db = Database(data_home=args.data_home)
    try:
        table = pq.read_table(args.input)
        n = db.insert_rows(args.table, table)
        print(f"imported {n} rows into {args.table}")
    finally:
        db.close()
    return 0


def cmd_datanode(args):
    """Run a standalone datanode process: a region server speaking Arrow
    Flight over shared storage (reference `greptime datanode start`).
    With --metasrv it registers its Flight address and heartbeats region
    stats (reference datanode/src/heartbeat.rs) so frontends discover it
    and the metasrv's failure detection has real input."""
    import signal
    import time as _time

    from .distributed.flight import DatanodeFlightServer
    from .storage.engine import TimeSeriesEngine
    from .utils.config import Config

    # layered config (env vars incl. GREPTIMEDB_TPU__REPLICA__SYNC_INTERVAL_MS,
    # which Config copies down to storage.follower_sync_interval_ms) with the
    # CLI data_home overriding whatever the layers said
    full_cfg = Config.load()
    storage_cfg = full_cfg.storage
    storage_cfg.data_home = args.data_home
    engine = TimeSeriesEngine(storage_cfg)
    # OTLP self-export: a bare datanode has no writer path for its own
    # spans (PR's trace table lives behind the SQL frontend), so when
    # trace.otlp_endpoint points at a frontend/standalone OTLP ingest,
    # ship the span ring there as protobuf batches instead
    otlp_task = None
    otlp_endpoint = getattr(full_cfg.trace, "otlp_endpoint", "")
    if otlp_endpoint:
        from .utils.self_trace import OtlpExportTask

        otlp_task = OtlpExportTask(
            otlp_endpoint, full_cfg.trace,
            service=f"greptimedb_tpu.datanode.{args.node_id}",
        ).start()
        print(f"otlp self-export -> {otlp_endpoint}", flush=True)
    host, port = (args.addr.rsplit(":", 1) + ["0"])[:2]
    server = DatanodeFlightServer(engine, f"grpc://{host}:{port}")
    import threading

    t = threading.Thread(target=server.serve, daemon=True)
    t.start()
    print(f"datanode {args.node_id} serving Flight at {server.location}", flush=True)
    stop = threading.Event()
    signal.signal(signal.SIGTERM, lambda *a: stop.set())
    signal.signal(signal.SIGINT, lambda *a: stop.set())

    meta = None
    if getattr(args, "metasrv", None):
        from .distributed.alive_keeper import RegionAliveKeeper
        from .distributed.meta_service import MetaClient

        meta = MetaClient(args.metasrv.split(","))
        flight_addr = server.location.removeprefix("grpc://")
        keeper = RegionAliveKeeper(args.node_id)

        def heartbeat_loop():
            import logging

            log = logging.getLogger("greptimedb_tpu.datanode")
            last_err = None
            while not stop.is_set():
                try:
                    now_ms = _time.time() * 1000
                    reply = meta.handle_heartbeat(
                        args.node_id,
                        [s.__dict__ for s in engine.region_statistics()],
                        now_ms,
                        addr=flight_addr,
                    )
                    keeper.renew(
                        reply.get("lease_regions", []),
                        reply.get("lease_until_ms", now_ms),
                    )
                    keeper.close_staled_regions(engine, now_ms)
                    last_err = None
                except Exception as e:  # noqa: BLE001 — metasrv may be electing
                    # log each DISTINCT failure once (a misconfiguration
                    # like a node-id/role conflict would otherwise spin
                    # silently forever at the heartbeat interval)
                    if str(e) != last_err:
                        last_err = str(e)
                        log.warning("heartbeat to metasrv failed: %s", e)
                    # the lease sweep runs EVEN when the metasrv is
                    # unreachable — a partitioned node's leases lapse on
                    # its own clock and its regions must close before the
                    # failed-over holder's compaction races ours
                    try:
                        keeper.close_staled_regions(engine, _time.time() * 1000)
                    except Exception:  # noqa: BLE001
                        pass
                    stop.wait(args.heartbeat_s)
                    continue
                # the metasrv drained its mailbox when it replied: apply
                # each instruction independently so one failure cannot
                # discard the rest of the batch (they are never requeued)
                for instr in reply.get("instructions", []):
                    try:
                        _apply_datanode_instruction(engine, instr)
                    except Exception:  # noqa: BLE001
                        log.warning("instruction %s failed", instr, exc_info=True)
                stop.wait(args.heartbeat_s)

        threading.Thread(target=heartbeat_loop, daemon=True).start()
    try:
        stop.wait()
    finally:
        if otlp_task is not None:
            otlp_task.stop()
        server.shutdown()
        engine.close()
    return 0


def _apply_datanode_instruction(engine, instr: dict):
    """Mailbox instructions from metasrv heartbeat replies (reference
    Instruction enum, common/meta/src/instruction.rs)."""
    kind = instr.get("kind")
    if kind == "open_region":
        engine.open_region(instr["region_id"])
    elif kind == "close_region":
        engine.close_region(instr["region_id"])
    elif kind == "flush_region":
        engine.flush_region(instr["region_id"])


def cmd_frontend(args):
    """Run a distributed frontend process: SQL over HTTP (+ MySQL) planned
    against metasrv routes and fanned out to Flight datanodes (reference
    `greptime frontend start`, frontend/src/instance.rs:110)."""
    import signal
    import threading
    import time as _time

    from .distributed.frontend import Frontend
    from .servers.http import HttpServer
    from .servers.mysql import MysqlServer

    fe = Frontend(
        args.data_home, args.metasrv.split(","), node_id=args.node_id
    )
    http = HttpServer(fe, args.http_addr).start(warm=False)
    mysql = None
    if args.mysql_addr:
        mysql = MysqlServer(fe, args.mysql_addr).start(warm=False)
    print(
        f"frontend {args.node_id} serving HTTP at {http.address}"
        + (f", MySQL at {mysql.address}" if mysql else ""),
        flush=True,
    )
    stop = threading.Event()
    signal.signal(signal.SIGTERM, lambda *a: stop.set())
    signal.signal(signal.SIGINT, lambda *a: stop.set())
    while not stop.is_set():
        fe.heartbeat()
        stop.wait(args.heartbeat_s)
    http.stop()
    if mysql:
        mysql.stop()
    fe.close()
    return 0


def cmd_flownode(args):
    """Run a flownode process: the flow engine with a Flight service for
    mirrored inserts + flow DDL, heartbeating to the metasrv (reference
    `greptime flownode start`, flow/src/server.rs)."""
    from .distributed.flownode import run_flownode

    return run_flownode(args.node_id, args.data_home, args.addr, args.metasrv)


def cmd_metasrv(args):
    """Run a metasrv process: routes/heartbeats/placement/migration over
    HTTP with lease-based election on the shared KV (reference
    `greptime metasrv start`).  Datanodes are reached through Flight using
    --datanode node_id=host:port mappings."""
    import signal
    import threading

    from .distributed.election import LeaseElection
    from .distributed.flight import FlightDatanodeClient
    from .distributed.kv import FileKvBackend
    from .distributed.meta_service import MetasrvServer
    from .distributed.metasrv import Metasrv

    peers = {}
    for spec in args.datanode or []:
        nid, addr = spec.split("=", 1)
        peers[int(nid)] = addr

    class RemoteNodeManager:
        """NodeManager over Flight clients (reference common/meta
        NodeManager backed by per-peer gRPC clients).  Addresses come
        from static --datanode mappings or, preferentially, from what
        nodes registered via heartbeat (node_address role-equivalent)."""

        metasrv = None  # wired after construction

        def _client(self, node_id: int) -> FlightDatanodeClient:
            addr = None
            if self.metasrv is not None:
                addr = self.metasrv.node_addresses().get(node_id)
            addr = addr or peers.get(node_id)
            if addr is None:
                raise ConnectionError(f"datanode {node_id} has no known address")
            return FlightDatanodeClient(node_id, f"grpc://{addr}")

        def open_region(self, node_id: int, rid: int):
            self._client(node_id).open_region(rid)

        def open_follower(self, node_id: int, rid: int):
            self._client(node_id).open_region(rid, writable=False)

        def close_region_quiet(self, node_id: int, rid: int):
            try:
                self._client(node_id).close_region(rid)
            except Exception:  # noqa: BLE001
                pass

        def flush_region(self, node_id: int, rid: int):
            self._client(node_id).flush_region(rid)

        def set_region_writable(self, node_id: int, rid: int, writable: bool):
            self._client(node_id).set_region_writable(rid, writable)

    if getattr(args, "etcd_endpoints", None):
        # wire-level deployment: cluster metadata AND leader election live
        # in etcd (lease + create-revision CAS) so multiple metasrv
        # processes coordinate without a shared filesystem
        from .remote.etcd import EtcdClient, EtcdElection, EtcdKvBackend

        kv = EtcdKvBackend(args.etcd_endpoints)
        election = EtcdElection(
            EtcdClient(args.etcd_endpoints), args.node_id
        )
    else:
        kv = FileKvBackend(args.kv_dir)
        election = LeaseElection(kv, args.node_id)
    node_manager = RemoteNodeManager()
    metasrv = Metasrv(kv, node_manager, election=election)
    node_manager.metasrv = metasrv
    for nid, addr in peers.items():
        metasrv.register_datanode(nid, addr)
    server = MetasrvServer(metasrv, args.addr).start()
    print(f"metasrv {args.node_id} serving at {server.address}", flush=True)

    stop = threading.Event()
    signal.signal(signal.SIGTERM, lambda *a: stop.set())
    signal.signal(signal.SIGINT, lambda *a: stop.set())
    # campaign + supervise loop (reference metasrv election/heartbeat loops)
    import time as _time

    while not stop.is_set():
        try:
            election.campaign()
            if metasrv.is_leader():
                metasrv.tick(_time.time() * 1000)
        except Exception:  # noqa: BLE001 — supervision must outlive one bad tick
            import logging as _logging

            _logging.getLogger("greptimedb_tpu.metasrv").warning(
                "supervisor tick failed; retrying", exc_info=True
            )
        stop.wait(1.0)
    server.stop()
    return 0


def cmd_metadata(args):
    """metadata snapshot/restore/info (reference cli/src/metadata/:
    `greptime cli metadata snapshot save|restore` + control info).  The
    snapshot captures the catalog (tables, views, partition rules) and the
    per-table dictionaries index — enough to rebuild metadata after a
    catalog-file loss; region data (SSTs/WAL/manifests) is storage-level
    and restored by region replay, as in the reference."""
    import json
    import os
    import shutil

    catalog_path = os.path.join(args.data_home, "catalog.json")
    if args.action == "snapshot":
        if not os.path.exists(catalog_path):
            print(f"no catalog at {catalog_path}")
            return 1
        os.makedirs(os.path.dirname(os.path.abspath(args.out)) or ".", exist_ok=True)
        shutil.copyfile(catalog_path, args.out)
        with open(catalog_path) as f:
            state = json.load(f)
        n_tables = sum(len(ts) for ts in state.get("databases", {}).values())
        n_views = sum(len(vs) for vs in state.get("views", {}).values())
        print(f"snapshot written to {args.out}: {n_tables} tables, {n_views} views")
        return 0
    if args.action == "restore":
        with open(args.snapshot) as f:
            state = json.load(f)  # validates JSON before overwriting anything
        os.makedirs(args.data_home, exist_ok=True)
        tmp = catalog_path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(state, f)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, catalog_path)
        print(f"catalog restored from {args.snapshot}")
        return 0
    if args.action == "info":
        if not os.path.exists(catalog_path):
            print(f"no catalog at {catalog_path}")
            return 1
        with open(catalog_path) as f:
            state = json.load(f)
        for db_name, tables in sorted(state.get("databases", {}).items()):
            for name, meta in sorted(tables.items()):
                print(f"table {db_name}.{name} id={meta.get('table_id')}")
            for vname in sorted(state.get("views", {}).get(db_name, {})):
                print(f"view  {db_name}.{vname}")
        return 0
    return 1


def cmd_objbench(args):
    """Object-storage micro-benchmark (reference `greptime datanode
    objbench`, cmd/src/datanode/objbench.rs): timed write/read/list/delete
    rounds against the configured store."""
    import json
    import time

    from .storage.object_store import build_object_store
    from .utils.config import StorageConfig

    cfg = StorageConfig(data_home=args.data_home)
    cfg.store_type = args.store_type
    store = build_object_store(cfg)
    payload = b"\xab" * (args.size_kb << 10)
    n = args.num_objects
    t0 = time.perf_counter()
    for i in range(n):
        store.write(f"objbench/{i:06d}.bin", payload)
    t_write = time.perf_counter() - t0
    t0 = time.perf_counter()
    total = 0
    for i in range(n):
        total += len(store.read(f"objbench/{i:06d}.bin"))
    t_read = time.perf_counter() - t0
    t0 = time.perf_counter()
    listed = len(store.list("objbench"))
    t_list = time.perf_counter() - t0
    t0 = time.perf_counter()
    for i in range(n):
        store.delete(f"objbench/{i:06d}.bin")
    t_delete = time.perf_counter() - t0
    print(json.dumps({
        "store_type": args.store_type,
        "objects": n,
        "object_kb": args.size_kb,
        "write_mb_s": round(n * args.size_kb / 1024 / max(t_write, 1e-9), 1),
        "read_mb_s": round(total / (1 << 20) / max(t_read, 1e-9), 1),
        "list_ms": round(t_list * 1000, 2),
        "listed": listed,
        "delete_per_s": round(n / max(t_delete, 1e-9)),
    }))
    return 0


def main(argv=None):
    parser = argparse.ArgumentParser(prog="greptimedb-tpu")
    sub = parser.add_subparsers(dest="command", required=True)

    p = sub.add_parser("standalone", help="start the all-in-one server")
    p.add_argument("action", choices=["start"])
    p.add_argument("--config", default=None, help="TOML config path")
    p.add_argument("--data-home", default=None)
    p.add_argument("--http-addr", default=None)
    p.add_argument("--mysql-addr", default=None)
    p.add_argument("--postgres-addr", default=None)
    p.set_defaults(fn=cmd_standalone)

    p = sub.add_parser("sql", help="execute SQL against a data dir")
    p.add_argument("query", nargs="?", default=None, help="SQL text (stdin if omitted)")
    p.add_argument("--data-home", default="./greptimedb_data")
    p.add_argument("--pretty", action="store_true")
    p.set_defaults(fn=cmd_sql)

    p = sub.add_parser("export", help="export a table to Parquet")
    p.add_argument("table")
    p.add_argument("output")
    p.add_argument("--data-home", default="./greptimedb_data")
    p.set_defaults(fn=cmd_export)

    p = sub.add_parser("import", help="import Parquet into a table")
    p.add_argument("table")
    p.add_argument("input")
    p.add_argument("--data-home", default="./greptimedb_data")
    p.set_defaults(fn=cmd_import)

    p = sub.add_parser("datanode", help="start a datanode (Flight region server)")
    p.add_argument("action", choices=["start"])
    p.add_argument("--node-id", type=int, default=0)
    p.add_argument("--data-home", default="./greptimedb_data")
    p.add_argument("--addr", default="127.0.0.1:0")
    p.add_argument("--metasrv", default=None,
                   help="comma-separated metasrv addrs to register with + heartbeat")
    p.add_argument("--heartbeat-s", type=float, default=1.0)
    p.set_defaults(fn=cmd_datanode)

    p = sub.add_parser(
        "frontend",
        help="start a distributed frontend (HTTP/MySQL over Flight datanodes)",
    )
    p.add_argument("action", choices=["start"])
    p.add_argument("--node-id", type=int, default=100)
    p.add_argument("--data-home", required=True,
                   help="shared storage root (catalog lives here)")
    p.add_argument("--metasrv", required=True,
                   help="comma-separated metasrv addrs")
    p.add_argument("--http-addr", default="127.0.0.1:0")
    p.add_argument("--mysql-addr", default=None)
    p.add_argument("--heartbeat-s", type=float, default=1.0)
    p.set_defaults(fn=cmd_frontend)

    p = sub.add_parser("flownode", help="start a flownode (streaming/batching flows)")
    p.add_argument("start", choices=["start"])
    p.add_argument("--node-id", type=int, default=1)
    p.add_argument("--data-home", required=True)
    p.add_argument("--addr", default="127.0.0.1:0")
    p.add_argument("--metasrv", default=None, help="metasrv addr for heartbeats")
    p.set_defaults(fn=cmd_flownode)

    p = sub.add_parser("metasrv", help="start a metasrv (routes/heartbeats/election)")
    p.add_argument("action", choices=["start"])
    p.add_argument("--node-id", default="metasrv-0")
    p.add_argument("--kv-dir", default="./greptimedb_meta")
    p.add_argument("--addr", default="127.0.0.1:0")
    p.add_argument(
        "--datanode", action="append",
        help="node_id=host:port mapping (repeatable)",
    )
    p.add_argument(
        "--etcd-endpoints", default="",
        help="etcd v3 grpc-gateway endpoints (host:port[,host:port]); "
        "replaces --kv-dir with a wire-level KV + election backend",
    )
    p.set_defaults(fn=cmd_metasrv)

    p = sub.add_parser("metadata", help="catalog snapshot / restore / info")
    p.add_argument("action", choices=["snapshot", "restore", "info"])
    p.add_argument("--data-home", default="./greptimedb_data")
    p.add_argument("--out", default="./catalog_snapshot.json", help="snapshot output path")
    p.add_argument("--snapshot", default="./catalog_snapshot.json", help="snapshot to restore")
    p.set_defaults(fn=cmd_metadata)

    p = sub.add_parser("objbench", help="object-storage micro-benchmark")
    p.add_argument("--data-home", default="/tmp/greptimedb_objbench")
    p.add_argument("--store-type", default="fs", choices=["fs", "memory"])
    p.add_argument("--num-objects", type=int, default=64)
    p.add_argument("--size-kb", type=int, default=1024)
    p.set_defaults(fn=cmd_objbench)

    args = parser.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
