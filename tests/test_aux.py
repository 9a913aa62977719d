"""Auxiliary subsystem tests: parser facade, checkpoint/resume, logger
stream encoding, kubeipresolver/kubemanager operators, netns helpers."""

import dataclasses
import json

import numpy as np
import jax.numpy as jnp
import pytest

import inspektor_gadget_tpu.all_gadgets  # noqa: F401
from inspektor_gadget_tpu.columns import Columns, col
from inspektor_gadget_tpu.parser import Parser
from inspektor_gadget_tpu.types import Event


@dataclasses.dataclass
class Ev(Event):
    comm: str = col("", width=16)
    pid: int = col(0, width=7, dtype=np.int32)
    reads: int = col(0, width=8, group="sum", dtype=np.int64)


def test_parser_filter_sort_callback():
    p = Parser(Columns(Ev))
    p.set_filters("comm:bash")
    p.set_sort("-reads")
    got = []
    p.set_event_callback(got.append)
    p.event_handler(Ev(comm="bash", pid=1, reads=5))
    p.event_handler(Ev(comm="curl", pid=2, reads=9))
    assert len(got) == 1 and got[0].comm == "bash"

    arrays = []
    p.set_event_callback_array(arrays.append)
    p.event_handler_array([Ev(comm="bash", reads=1), Ev(comm="bash", reads=7),
                           Ev(comm="zsh", reads=3)])
    assert [e.reads for e in arrays[0]] == [7, 1]


def test_parser_json_handlers_and_snapshots():
    p = Parser(Columns(Ev))
    got = []
    p.set_event_callback(got.append)
    p.json_handler("node-9")(json.dumps({"comm": "x", "pid": 3}))
    assert got[0].node == "node-9" and got[0].pid == 3

    p.enable_snapshots(ttl_ticks=2)
    arrays = []
    p.set_event_callback_array(arrays.append)
    p.json_handler_array("n1")(json.dumps([{"comm": "a", "reads": 1}]))
    p.json_handler_array("n2")(json.dumps([{"comm": "b", "reads": 2}]))
    p.tick()
    assert {e.comm for e in arrays[0]} == {"a", "b"}


def test_parser_oneshot_accumulate_flush():
    p = Parser(Columns(Ev))
    arrays = []
    p.set_event_callback_array(arrays.append)
    p.accumulate([Ev(comm="a")])
    p.accumulate([Ev(comm="b")])
    assert not arrays
    p.flush()
    assert len(arrays[0]) == 2


def test_checkpoint_roundtrip(tmp_path):
    from inspektor_gadget_tpu.ops import bundle_init, bundle_update, cms_query
    from inspektor_gadget_tpu.utils.checkpoint import load_pytree, save_pytree

    b = bundle_init(depth=4, log2_width=10, hll_p=8, entropy_log2_width=7, k=8)
    keys = jnp.array([7, 7, 9], dtype=jnp.uint32)
    b = bundle_update(b, keys, keys, keys, jnp.ones(3, bool))
    save_pytree(tmp_path / "sketch", b)
    restored = load_pytree(tmp_path / "sketch", bundle_init(
        depth=4, log2_width=10, hll_p=8, entropy_log2_width=7, k=8))
    assert float(restored.events) == 3
    q = cms_query(restored.cms, jnp.array([7], dtype=jnp.uint32))
    assert int(q[0]) == 2
    # resumed state keeps absorbing
    more = bundle_update(restored, keys, keys, keys, jnp.ones(3, bool))
    assert float(more.events) == 6


def test_checkpoint_structure_mismatch_rejected(tmp_path):
    """A checkpoint saved from one structure must not silently unflatten
    into a different `like` that happens to have the same leaf count —
    the saved treedef is validated on load."""
    import pytest as _pytest

    from inspektor_gadget_tpu.utils.checkpoint import load_pytree, save_pytree

    save_pytree(tmp_path / "pair", {"a": jnp.zeros(3), "b": jnp.ones(2)})
    with _pytest.raises(ValueError, match="structure mismatch"):
        load_pytree(tmp_path / "pair",
                    {"x": jnp.zeros(3), "y": jnp.ones(2)})


def test_stream_logger_severity_encoding():
    from inspektor_gadget_tpu.utils.logger import WARN, StreamLogger

    pushed = []
    sl = StreamLogger(lambda t, hdr, payload: pushed.append((t, hdr, payload)))
    sl.warn("careful")
    t, hdr, payload = pushed[0]
    assert t >> 16 == WARN
    assert hdr == {}  # no run/trace identity configured
    assert payload == b"careful"


def test_kubeipresolver_enriches_addresses():
    from inspektor_gadget_tpu.operators.kubeipresolver import KubeIPResolver
    from inspektor_gadget_tpu.operators.operators import get as get_op

    op: KubeIPResolver = get_op("kubeipresolver")
    op.set_inventory({"10.0.0.5": ("pod", "web-0")})

    @dataclasses.dataclass
    class NetEv:
        saddr: str = ""
        daddr: str = ""

    inst = op.instantiate(None, None, op.instance_params().to_params())
    ev = NetEv(saddr="10.0.0.5", daddr="8.8.8.8")
    inst.enrich(ev)
    assert "pod/web-0" in ev.saddr
    assert ev.daddr == "8.8.8.8"


def test_kubemanager_selector_filtering():
    from inspektor_gadget_tpu.containers import Container
    from inspektor_gadget_tpu.gadgets import GadgetContext, get
    from inspektor_gadget_tpu.operators.operators import get as get_op

    lm = get_op("localmanager")
    if lm.cc is None:
        lm.init(lm.global_params().to_params())
    lm.cc.add_container(Container(id="km1", name="web", pod="web-0",
                                  namespace="prod", mntns=555001, pid=1))
    lm.cc.add_container(Container(id="km2", name="db", pod="db-0",
                                  namespace="prod", mntns=555002, pid=1))

    km = get_op("kubemanager")
    desc = get("trace", "exec")
    ctx = GadgetContext(desc)
    params = km.instance_params().to_params()
    params.set("namespace", "prod")
    params.set("podname", "web-0")

    class FakeGadget:
        def __init__(self):
            self.filter = None

        def set_mntns_filter(self, ids):
            self.filter = ids

    from inspektor_gadget_tpu.gadgets.interface import MountNsFilterSetter
    g = FakeGadget()
    assert isinstance(g, MountNsFilterSetter)
    inst = km.instantiate(ctx, g, params)
    inst.pre_gadget_run()
    assert g.filter == {555001}
    inst.post_gadget_run()
    lm.cc.remove_container("km1")
    lm.cc.remove_container("km2")


def test_dnstester_builds_valid_query():
    from tools.dnstester import build_query

    pkt = build_query("a.example.com", qtype=28)
    assert pkt[:2] == b"\x12\x34"
    assert b"\x01a\x07example\x03com\x00" in pkt
    assert pkt.endswith(b"\x00\x1c\x00\x01")  # AAAA, IN


def test_runtime_client_detection_degrades():
    from inspektor_gadget_tpu.containers.runtime_client import (
        DockerClient, detect_runtime_client, with_runtime_enrichment)
    from inspektor_gadget_tpu.containers import ContainerCollection

    # no docker socket in this environment → probe must degrade cleanly
    assert DockerClient("/nonexistent.sock").available() is False
    detect_runtime_client()  # must not raise
    cc = ContainerCollection()
    cc.initialize(with_runtime_enrichment())  # silent no-op
    assert len(cc) >= 0


def test_windowed_example_scripts_importable():
    import examples.sketch_pipeline
    import examples.custom_gadget  # registers trace/heartbeat
    from inspektor_gadget_tpu.gadgets import get
    assert get("trace", "heartbeat").description
