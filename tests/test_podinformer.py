"""Pod informer tests (ref: pkg/container-collection/podinformer.go's
update-diff contract — containers appearing/vanishing in pod specs become
add/remove events on the collection)."""

import json

from inspektor_gadget_tpu.containers import (
    ContainerCollection,
    ContainerSelector,
    PodInformer,
    file_pod_source,
    with_fallback_pod_informer,
    with_fake_containers,
    with_pod_informer,
)
from inspektor_gadget_tpu.containers.container import Container


def pod(name, ns="default", node="node-a", containers=("main",), labels=None):
    return {
        "name": name, "namespace": ns, "uid": f"uid-{name}", "node": node,
        "labels": labels or {}, "containers": [{"name": c} for c in containers],
    }


def test_informer_diffs_adds_and_removes():
    pods = [pod("web", containers=("nginx", "sidecar"))]
    inf = PodInformer(lambda: pods, interval=999)
    added, removed = [], []
    inf.on_add = lambda c: added.append(c.name)
    inf.on_remove = lambda k: removed.append(k)
    assert inf.refresh() == (2, 0)
    assert sorted(added) == ["nginx", "sidecar"]
    # idempotent: same snapshot → no events
    assert inf.refresh() == (0, 0)
    # drop one container, add a pod
    pods[:] = [pod("web", containers=("nginx",)), pod("db", containers=("pg",))]
    assert inf.refresh() == (1, 1)
    assert added[-1] == "pg" and "sidecar" in removed[0]


def test_informer_node_filter_and_error_resilience():
    calls = {"n": 0}

    def source():
        calls["n"] += 1
        if calls["n"] == 2:
            raise OSError("apiserver blip")
        return [pod("web", node="node-a"), pod("other", node="node-b")]

    inf = PodInformer(source, node_name="node-a", interval=999)
    assert inf.refresh() == (1, 0)         # only node-a's pod
    assert inf.refresh() == (0, 0)         # source error → state untouched
    assert inf.refresh() == (0, 0)         # recovered, still consistent


def test_with_pod_informer_populates_collection(tmp_path):
    manifest = tmp_path / "pods.json"
    manifest.write_text(json.dumps({"pods": [
        pod("web", ns="prod", containers=("nginx",), labels={"app": "web"}),
    ]}))
    cc = ContainerCollection()
    cc.initialize(with_pod_informer(file_pod_source(str(manifest)),
                                    interval=999))
    got = cc.get_all(ContainerSelector(namespace="prod"))
    assert len(got) == 1
    assert got[0].pod == "web" and got[0].labels == {"app": "web"}
    cc._pod_informer.stop()


def test_informer_containers_survive_gadget_run(tmp_path):
    """Regression: attaching the informer via ensure_initialized must mark
    localmanager as initialized, or the first gadget run re-inits it and
    replaces the collection, orphaning every informer-discovered
    container."""
    import inspektor_gadget_tpu.all_gadgets  # noqa: F401  (registers ops)
    from inspektor_gadget_tpu.gadgets import GadgetContext, get
    from inspektor_gadget_tpu.operators.operators import ensure_initialized
    from inspektor_gadget_tpu.runtime import LocalRuntime

    manifest = tmp_path / "pods.json"
    manifest.write_text(json.dumps([pod("web", ns="prod",
                                        containers=("nginx",))]))
    lm = ensure_initialized("localmanager")
    with_pod_informer(file_pod_source(str(manifest)), node_name="node-a",
                      interval=999)(lm.cc)
    try:
        assert any(c.runtime == "podinformer" for c in lm.cc.get_all())

        desc = get("trace", "exec")
        params = desc.params().to_params()
        params.set("source", "pysynthetic")
        params.set("rate", "20000")
        ctx = GadgetContext(desc, gadget_params=params, timeout=0.3)
        result = LocalRuntime().run_gadget(ctx, on_event=lambda e: None)
        assert not result.errors()
        # same collection object, informer container still tracked
        assert any(c.runtime == "podinformer" for c in lm.cc.get_all())
    finally:
        lm.cc._pod_informer.stop()


def test_informer_survives_bad_pod_and_bad_subscriber():
    """Malformed pod dicts or raising callbacks must not kill discovery."""
    pods = [{"name": "ok", "namespace": "d", "uid": "u", "node": "",
             "labels": {}, "containers": [{"id": "x"}]}]  # no 'name' key
    inf = PodInformer(lambda: pods, interval=999)
    assert inf.refresh() == (0, 0)  # malformed → state untouched, no raise
    pods[0]["containers"] = [{"name": "good"}]
    inf.on_add = lambda c: (_ for _ in ()).throw(RuntimeError("subscriber"))
    assert inf.refresh() == (1, 0)  # callback raised, informer kept going
    assert inf.refresh() == (0, 0)  # state consistent afterwards


def test_agent_serve_with_pod_manifest(tmp_path):
    """Black-box: agent discovers containers from a watched pod manifest;
    DumpState exposes them (ref: DumpState dumps containers,
    gadgettracermanager.go:204-219)."""
    import json as _json
    import subprocess
    import sys
    import time

    manifest = tmp_path / "pods.json"
    manifest.write_text(json.dumps([pod("web", ns="prod",
                                        containers=("nginx",))]))
    sock = f"unix://{tmp_path}/agent.sock"
    proc = subprocess.Popen(
        [sys.executable, "-m", "inspektor_gadget_tpu.agent.main", "serve",
         "--listen", sock, "--node-name", "node-a",
         "--pod-manifest", str(manifest), "--informer-interval", "0.2",
         "--platform", "cpu"],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
    try:
        deadline = time.time() + 60
        found = None
        while time.time() < deadline and found is None:
            r = subprocess.run(
                [sys.executable, "-m", "inspektor_gadget_tpu.agent.main",
                 "dump", "--target", sock],
                capture_output=True, text=True, timeout=30)
            if r.returncode == 0:
                dump = _json.loads(r.stdout)
                # procfs discovery may contribute other containers; find ours
                found = next((c for c in dump.get("containers", ())
                              if c["runtime"] == "podinformer"), None)
            if found is None:
                time.sleep(0.5)
        assert found, "pod-informer container never appeared in DumpState"
        assert found["name"] == "nginx" and found["namespace"] == "prod"
    finally:
        proc.terminate()
        proc.wait(timeout=10)


def test_fallback_informer_only_when_empty(tmp_path):
    manifest = tmp_path / "pods.json"
    manifest.write_text(json.dumps([pod("web")]))
    # collection already populated by another backend → fallback is inert
    cc = ContainerCollection()
    cc.initialize(
        with_fake_containers([Container(id="c1", name="c1")]),
        with_fallback_pod_informer(file_pod_source(str(manifest)),
                                   interval=999),
    )
    assert {c.id for c in cc.get_all()} == {"c1"}
    # empty collection → fallback activates
    cc2 = ContainerCollection()
    cc2.initialize(with_fallback_pod_informer(file_pod_source(str(manifest)),
                                              interval=999))
    assert len(cc2.get_all()) == 1
    cc2._pod_informer.stop()
