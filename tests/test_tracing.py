"""Distributed tracing: blkin-style spans across client -> primary ->
replicas/shards (ref: src/common/zipkin_trace.h, Message.h:263,
OpRequest::pg_trace into ECBackend.cc:1508)."""
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


def test_ec_decode_span_splits_into_stage_and_kernel_children():
    """The ec_decode_kernel span carries `stage` (host survivor
    gather) and `kernel` (device decode) CHILD spans, so the
    decode_incl_stage gap is visible per op in
    assembled traces."""
    import sys
    sys.path.insert(0, __file__.rsplit("/", 1)[0])
    from test_ec_backend import Cluster, _payload
    from ceph_tpu.common.tracing import span_tree

    cl = Cluster()
    tracer = Tracer("osd.0")
    cl.backend.tracer = tracer
    data = _payload(2 * cl.backend.sinfo.stripe_width)
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
    kids = [s for s in spans if s["parent"] == parents[0]["span_id"]]
    names = sorted(k["name"] for k in kids)
    assert names == ["kernel", "stage"]
    for k in kids:
        assert 0 <= k["duration"] <= parents[0]["duration"] + 1e-6
    # the tree renders with the children nested under the decode span
    tree = span_tree(spans)
    node = [n for n in tree if n["name"] == "ec_decode_kernel"]
    assert node and len(node[0]["children"]) == 2
