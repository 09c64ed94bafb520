"""Distributed tracing: blkin-style spans across client -> primary ->
replicas/shards (ref: src/common/zipkin_trace.h, Message.h:263,
OpRequest::pg_trace into ECBackend.cc:1508)."""
import numpy as np
import pytest

from ceph_tpu.common.options import global_config
from ceph_tpu.common.tracing import Tracer, child_of, new_trace
from ceph_tpu.testing import MiniCluster


def test_span_primitives():
    root = new_trace()
    child = child_of(root)
    assert child["trace_id"] == root["trace_id"]
    assert child["parent"] == root["span"]
    assert child_of(None) is None
    ids = {child_of(root)["span"] for _ in range(10000)}
    assert len(ids) == 10000
    assert all(len(i) == 16 and int(i, 16) >= 0 for i in ids)
    t = Tracer("osd.0", keep=2)
    assert t.start_span(None, "x") is None     # tracing off: no-op
    for i in range(3):
        sp = t.start_span(new_trace(), f"op{i}")
        sp.event("did a thing")
        t.finish(sp)
    dumped = t.dump()
    assert len(dumped) == 2                    # ring bounded
    assert dumped[-1]["name"] == "op2"
    assert dumped[-1]["events"][0]["event"] == "did a thing"
    assert dumped[-1]["duration"] >= 0


@pytest.mark.parametrize("pool_kind", ["replicated", "erasure"])
def test_cross_daemon_trace(pool_kind):
    """One traced client write produces spans on the CLIENT (the
    objecter roots the trace), the primary, and every replica/shard
    daemon — plus the encode-kernel span on an EC pool — all stitched
    by trace_id with correct parent links."""
    c = MiniCluster(n_osd=4, threaded=True)
    cfg = global_config()
    try:
        c.wait_all_up()
        r = c.rados()
        if pool_kind == "erasure":
            r.mon_command({"prefix": "osd erasure-code-profile set",
                           "name": "k2m1",
                           "profile": {"plugin": "tpu", "k": "2",
                                       "m": "1",
                                       "crush-failure-domain": "osd"}})
            r.pool_create("tp", pg_num=8, pool_type="erasure",
                          erasure_code_profile="k2m1")
        else:
            r.pool_create("tp", pg_num=8)
        io = r.open_ioctx("tp")
        cfg.set("blkin_trace_all", True)
        io.write_full("traced", b"follow me" * 200)
        cfg.set("blkin_trace_all", False)
        client_spans = r.objecter.dump_traces()
        spans = client_spans + \
            [s for d in c.osds.values() for s in d.tracer.dump()]
        # the objecter leg is the trace root
        roots = [s for s in client_spans
                 if s["name"].startswith("objecter_op")
                 and s["parent"] is None]
        assert len(roots) == 1
        root = roots[0]
        tid = root["trace_id"]
        spans = [s for s in spans if s["trace_id"] == tid]
        # every send attempt lands an osd_op child under the client
        # span; the successful one carries reply_sent
        prim = [s for s in spans if s["name"].startswith("osd_op")
                and any(e["event"] == "reply_sent"
                        for e in s["events"])]
        assert len(prim) == 1
        assert prim[0]["parent"] == root["span_id"]
        sub = "rep_write" if pool_kind == "replicated" \
            else "ec_sub_write"
        kids = [s for s in spans if s["name"] == sub]
        # replicated: 2 remote replicas; EC: 2 remote shards (the
        # primary's own shard applies inline, no message)
        assert len(kids) == 2
        assert all(k["parent"] == prim[0]["span_id"] for k in kids)
        services = {k["service"] for k in kids}
        assert prim[0]["service"] not in services
        if pool_kind == "erasure":
            # the Pallas encode region gets its OWN span on the
            # primary, so staged-encode cost is visible per stage
            enc = [s for s in spans
                   if s["name"] == "ec_encode_kernel"]
            assert len(enc) == 1
            assert enc[0]["parent"] == prim[0]["span_id"]
            assert enc[0]["service"] == prim[0]["service"]
        # the assembled tree renders with the client span as the root
        from ceph_tpu.common.tracing import format_tree, span_tree
        trees = span_tree(spans)
        top = [t for t in trees if t["span_id"] == root["span_id"]]
        assert len(top) == 1
        assert any("osd_op" in ln for ln in format_tree(spans))
    finally:
        cfg.set("blkin_trace_all", False)
        c.shutdown()


def test_trace_context_survives_tcp_wire():
    """The Message `trace` field rides the versioned TCP frame codec
    byte-faithfully (ref: Message.h:263 — the blkin trace is part of
    the wire envelope, not an in-process convenience)."""
    from ceph_tpu.msg import encoding as wire
    from ceph_tpu.msg.messages import ECSubWrite, OSDOp

    ctx = new_trace()
    child = child_of(ctx)
    msg = OSDOp(oid="o", op="write", tid=7, data=b"x", trace=child)
    back = wire.decode_message(wire.encode_message(msg))
    assert back.trace == child
    assert back.trace["parent"] == ctx["span"]
    sub = ECSubWrite(tid=9, shard=1, trace=child_of(child))
    back = wire.decode_message(wire.encode_message(sub))
    assert back.trace["trace_id"] == ctx["trace_id"]
    assert back.trace["parent"] == child["span"]
    # untraced messages stay untraced over the wire
    assert wire.decode_message(
        wire.encode_message(OSDOp(oid="o"))).trace is None


#: the timed regions of one EC call on the batched (device) path
EC_STAGES = ["d2h", "device", "h2d", "stage", "unstage"]


def _ec_backend_cluster():
    import sys
    sys.path.insert(0, __file__.rsplit("/", 1)[0])
    from test_ec_backend import Cluster, _payload
    cl = Cluster()
    cl.backend.tracer = Tracer("osd.0")
    return cl, _payload(4 * cl.backend.sinfo.stripe_width)


def _children(spans, parent):
    return [s for s in spans if s["parent"] == parent["span_id"]]


def test_ec_decode_span_splits_into_stage_and_kernel_children():
    """The ec_decode_kernel span carries `stage` (host survivor
    gather), `h2d`, `device`, `d2h` and `unstage` CHILD spans, so host
    staging, transfers and the device decode are visible per op in
    assembled traces."""
    from ceph_tpu.common.tracing import span_tree

    cl, data = _ec_backend_cluster()
    tracer = cl.backend.tracer
    assert cl.write("obj", 0, data)
    cl.kill(1)          # degraded read: reconstruction must run
    out = {}
    cl.backend.objects_read_and_reconstruct(
        {"obj": (0, 0)},
        lambda r, e: out.update(results=r, errors=e),
        trace=new_trace())
    assert out["results"]["obj"] == data
    spans = tracer.dump()
    parents = [s for s in spans if s["name"] == "ec_decode_kernel"]
    assert len(parents) == 1
    kids = _children(spans, parents[0])
    assert sorted(k["name"] for k in kids) == EC_STAGES
    for k in kids:
        assert 0 <= k["duration"] <= parents[0]["duration"] + 1e-6
    # the tree renders with the children nested under the decode span
    tree = span_tree(spans)
    node = [n for n in tree if n["name"] == "ec_decode_kernel"]
    assert node and len(node[0]["children"]) == len(EC_STAGES)


@pytest.mark.parametrize("call", ["ec_encode_kernel", "ec_decode_kernel"])
def test_ec_call_stages_cover_the_call(call):
    """The five regions of a traced encode or degraded decode follow
    one another inside the call's span and cover at least 90 % of it
    (host staging, both transfers, the device work, unstaging)."""
    cl, data = _ec_backend_cluster()
    tracer = cl.backend.tracer
    for rnd in range(2):          # the first round compiles
        oid = f"obj{rnd}"
        done = {}
        cl.backend.submit_transaction(
            oid, [("write", 0, data)], lambda ok: done.update(ok=ok),
            trace=new_trace())
        assert done["ok"]
    cl.kill(1)
    for rnd in range(2):
        out = {}
        cl.backend.objects_read_and_reconstruct(
            {f"obj{rnd}": (0, 0)},
            lambda r, e: out.update(results=r), trace=new_trace())
        assert out["results"][f"obj{rnd}"] == data
    spans = tracer.dump()
    last = [s for s in spans if s["name"] == call][-1]
    kids = sorted(_children(spans, last), key=lambda s: s["start"])
    assert sorted(k["name"] for k in kids) == EC_STAGES
    assert [k["name"] for k in kids] == \
        ["stage", "h2d", "device", "d2h", "unstage"]
    for a, b in zip(kids, kids[1:]):
        assert a["end"] <= b["start"] + 1e-9
    assert last["start"] <= kids[0]["start"]
    assert kids[-1]["end"] <= last["end"]
    covered = sum(k["end"] - k["start"] for k in kids)
    assert covered >= 0.9 * (last["end"] - last["start"])


def test_ec_stage_timings_leave_the_bytes_unchanged():
    """ecutil with and without `timings`: the same shards and the same
    decoded stream; the timed call fills every region."""
    from ceph_tpu.ec import registry
    from ceph_tpu.osd import ecutil
    rng = np.random.default_rng(7)
    ec = registry.factory("tpu", {"k": "4", "m": "2"})
    sinfo = ecutil.StripeInfo(4, 4 * 4096)
    data = rng.bytes(3 * sinfo.stripe_width)
    plain = ecutil.encode(sinfo, ec, data)
    t_enc: dict = {}
    assert ecutil.encode(sinfo, ec, data, timings=t_enc) == plain
    survivors = {i: plain[i] for i in (0, 2, 3, 5)}
    t_dec: dict = {}
    assert ecutil.decode_concat(sinfo, ec, survivors) == data
    assert ecutil.decode_concat(sinfo, ec, survivors,
                                timings=t_dec) == data
    for t in (t_enc, t_dec):
        assert sorted(t) == EC_STAGES
        assert all(a <= b for a, b in t.values())


def test_ec_backend_counts_dispatches_and_transfer_bytes():
    """Each encode and each decode that reached the device counts one
    dispatch and its bytes each way, traced or not; a read with every
    data shard dispatches nothing."""
    from ceph_tpu.common.perf_counters import PerfCounters
    from ceph_tpu.osd.ec_backend import ECBackend
    cl, data = _ec_backend_cluster()
    cl.backend.tracer = None
    perf = PerfCounters("t")
    for key in ECBackend.PERF_KEYS:
        perf.add_u64_counter(key)
    cl.backend.perf = perf

    def counts():
        return [perf.get(k) for k in
                ("ec_dispatches", "ec_h2d_bytes", "ec_d2h_bytes")]

    k, m = cl.k, cl.m
    assert cl.write("obj", 0, data)
    assert counts() == [1, len(data), len(data) // k * m]
    assert cl.read("obj") == data
    assert counts() == [1, len(data), len(data) // k * m]
    cl.kill(1)
    assert cl.read("obj") == data
    shard = len(data) // k
    assert counts() == [2, len(data) + k * shard,
                        len(data) // k * m + shard]


def _ec_cluster_run():
    """Untraced then traced EC writes of one payload, an OSD holding a
    data shard killed, then an untraced and a traced degraded read,
    on a threaded MiniCluster (k=2, m=2)."""
    cfg = global_config()
    c = MiniCluster(n_osd=5, threaded=True)
    run: dict = {}
    try:
        c.wait_all_up()
        r = c.rados()
        r.mon_command({"prefix": "osd erasure-code-profile set",
                       "name": "k2m2",
                       "profile": {"plugin": "tpu", "k": "2", "m": "2",
                                   "crush-failure-domain": "osd"}})
        r.pool_create("tp", pg_num=8, pool_type="erasure",
                      erasure_code_profile="k2m2")
        io = r.open_ioctx("tp")
        payload = bytes(range(256)) * 96
        run["payload"] = payload

        def tracers():
            return [r.objecter.tracer] + \
                [d.tracer for d in c.osds.values()]

        def all_spans():
            return [s for t in tracers() for s in t.dump()]

        def shards(name):
            out = {}
            for d in c.osds.values():
                for cid in d.store.list_collections():
                    for oid in d.store.collection_list(cid):
                        if oid.name == name and oid.shard >= 0 and \
                                oid.snap == -2:
                            out[oid.shard] = d.store.read(cid, oid)
            return out

        io.write_full("plain", payload)
        run["untraced_write_spans"] = all_spans()
        cfg.set("blkin_trace_all", True)
        try:
            io.write_full("traced", payload)
        finally:
            cfg.set("blkin_trace_all", False)
        run["shards"] = (shards("plain"), shards("traced"))
        pid = r.pool_lookup("tp")
        omap = r.objecter.osdmap
        acting = omap.pg_to_up_acting_osds(
            omap.object_locator_to_pg("traced", pid))[2]
        victim = acting[1]               # data shard 1, not the primary
        c.kill_osd(victim)
        r.mon_command({"prefix": "osd down", "ids": [str(victim)]})
        import time
        end = time.monotonic() + 30
        while r.objecter.osdmap.is_up(victim):
            assert time.monotonic() < end, "victim never marked down"
            time.sleep(0.05)
        before = len(all_spans())
        run["untraced_read"] = io.read("traced")
        run["untraced_read_new_spans"] = len(all_spans()) - before
        cfg.set("blkin_trace_all", True)
        try:
            run["traced_read"] = io.read("traced")
        finally:
            cfg.set("blkin_trace_all", False)
        run["spans"] = all_spans()
    finally:
        cfg.set("blkin_trace_all", False)
        c.shutdown()
    return run


@pytest.fixture(scope="module")
def ec_cluster_run():
    return _ec_cluster_run()


def _op_roots(spans, op):
    return [s for s in spans if s["name"] == f"objecter_op:{op}"]


#: (queue span, the op it belongs to, the span it sits under)
QUEUE_SPANS = [
    ("ms_queue:OSDOp", "write_full", "objecter_op"),
    ("ms_queue:OSDOpReply", "write_full", "objecter_op"),
    ("ms_queue:ECSubWrite", "write_full", "osd_op"),
    ("ms_queue:ECSubWriteReply", "write_full", "osd_op"),
    ("ms_queue:OSDOp", "read", "objecter_op"),
    ("ms_queue:OSDOpReply", "read", "objecter_op"),
    ("ms_queue:ECSubRead", "read", "osd_op"),
    ("ms_queue:ECSubReadReply", "read", "osd_op"),
]


@pytest.mark.parametrize("name,op,under", QUEUE_SPANS)
def test_queue_span_sits_under_its_parent(ec_cluster_run, name, op,
                                          under):
    """Each messenger hop of a traced EC write and degraded read leaves
    a `ms_queue:<type>` span, beside the handler's span: under the
    client's objecter_op for the client's messages, under the
    primary's osd_op for the sub-ops and their replies, and inside
    that parent's interval."""
    spans = ec_cluster_run["spans"]
    roots = _op_roots(spans, op)
    assert len(roots) == 1
    mine = [s for s in spans if s["trace_id"] == roots[0]["trace_id"]]
    by_id = {s["span_id"]: s for s in mine}
    found = [s for s in mine if s["name"] == name]
    assert found
    for q in found:
        parent = by_id[q["parent"]]
        assert parent["name"].startswith(under)
        assert parent["start"] <= q["start"] <= q["end"] <= parent["end"]


def test_traced_cluster_ops_carry_ec_stage_children(ec_cluster_run):
    """On the cluster too, the traced write's encode and the degraded
    read's decode carry the five regions."""
    spans = ec_cluster_run["spans"]
    for call in ("ec_encode_kernel", "ec_decode_kernel"):
        calls = [s for s in spans if s["name"] == call]
        assert len(calls) == 1
        assert sorted(k["name"] for k in _children(spans, calls[0])) \
            == EC_STAGES


def test_untraced_ops_record_nothing_and_match_traced_bytes(
        ec_cluster_run):
    """With blkin_trace_all off no tracer records a span, and the
    shards a write stores and the bytes a degraded read returns are
    those of the traced run."""
    run = ec_cluster_run
    assert run["untraced_write_spans"] == []
    assert run["untraced_read_new_spans"] == 0
    plain, traced = run["shards"]
    assert len(plain) == 4 and plain == traced
    assert run["untraced_read"] == run["traced_read"] == run["payload"]


def test_client_op_wait_feeds_the_dequeue_histogram():
    """Every client op, traced or not, observes its wait from the
    messenger's queue to dispatch in op_before_dequeue_op_lat."""
    c = MiniCluster(n_osd=3, threaded=True)
    try:
        c.wait_all_up()
        r = c.rados()
        r.pool_create("rp", pg_num=4)
        io = r.open_ioctx("rp")
        for i in range(3):
            io.write_full(f"o{i}", b"x" * 100)
        counts = [d.perf.dump()["op_before_dequeue_op_lat"]["count"]
                  for d in c.osds.values()]
        assert sum(counts) >= 3
    finally:
        c.shutdown()


def test_recv_stamp_stays_off_the_wire():
    """A stamped message crosses a TCP connection without its stamp:
    the stamp is the receiver's, never encoded."""
    import time
    from ceph_tpu.msg.messages import OSDOp
    from ceph_tpu.msg.messenger import Dispatcher, Messenger
    from ceph_tpu.msg.tcp import TcpNet, pick_free_ports

    class Collector(Dispatcher):
        def __init__(self):
            self.got = []

        def ms_dispatch(self, msg):
            self.got.append(msg)
            return True

    ports = pick_free_ports(2)
    net = TcpNet({"a": ("127.0.0.1", ports[0]),
                  "b": ("127.0.0.1", ports[1])})
    ma, mb = Messenger.create(net, "a"), Messenger.create(net, "b")
    cb = Collector()
    mb.add_dispatcher(cb)
    ma.start()
    mb.start()
    try:
        msg = OSDOp(oid="o", op="write", tid=3, trace=new_trace())
        msg.recv_stamp = 12.5
        assert ma.connect("b").send_message(msg)
        end = time.monotonic() + 10
        while not cb.got and time.monotonic() < end:
            time.sleep(0.01)
        assert cb.got and cb.got[0].tid == 3
        assert cb.got[0].recv_stamp is None
        assert cb.got[0].trace == msg.trace
    finally:
        ma.shutdown()
        mb.shutdown()


def test_span_dump_and_tree_carry_the_timeline():
    """Dumped spans carry start and end; the rendered tree gives each
    span's offset from its root."""
    from ceph_tpu.common.tracing import format_tree, sibling_of
    t = Tracer("osd.0")
    root = new_trace()
    t.record_span(root, "objecter_op:write", 10.0, 10.5)
    t.record_span(sibling_of(child_of(root)), "ms_queue:OSDOp",
                  10.1, 10.25)
    d = {s["name"]: s for s in t.dump()}
    assert d["ms_queue:OSDOp"]["start"] == 10.1
    assert d["ms_queue:OSDOp"]["end"] == 10.25
    assert d["ms_queue:OSDOp"]["parent"] == root["span"]
    lines = format_tree(t.dump())
    assert lines[0].startswith("objecter_op:write [osd.0] +0.000000s")
    assert "ms_queue:OSDOp [osd.0] +0.100000s 0.150000s" in lines[1]
