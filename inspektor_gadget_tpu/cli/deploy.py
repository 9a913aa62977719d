"""deploy/undeploy: render + manage the per-node agent rollout.

Reference contract: cmd/kubectl-gadget/deploy.go (546 LoC) renders embedded
manifests (DaemonSet, ServiceAccount, RBAC, CRD — pkg/resources/manifests)
and applies them, waiting for rollout; undeploy.go removes them. Without a
live kube API here, `deploy --render` emits the equivalent manifests
(DaemonSet running the agent with TPU resources + hostPID for capture,
RBAC, namespace) for kubectl, and `deploy --local n` starts n local agent
daemons for development — the minikube analogue.
"""

from __future__ import annotations

AGENT_IMAGE = "ghcr.io/inspektor-gadget-tpu/agent:latest"
NAMESPACE = "ig-tpu"


def render_manifests(image: str = AGENT_IMAGE, namespace: str = NAMESPACE,
                     tpu_resource: str = "google.com/tpu",
                     tpus_per_node: int = 4) -> str:
    return f"""apiVersion: v1
kind: Namespace
metadata:
  name: {namespace}
---
apiVersion: v1
kind: ServiceAccount
metadata:
  name: ig-tpu-agent
  namespace: {namespace}
---
apiVersion: rbac.authorization.k8s.io/v1
kind: ClusterRole
metadata:
  name: ig-tpu-agent
rules:
- apiGroups: [""]
  resources: [pods, services, nodes]
  verbs: [get, list, watch]
---
apiVersion: rbac.authorization.k8s.io/v1
kind: ClusterRoleBinding
metadata:
  name: ig-tpu-agent
roleRef:
  apiGroup: rbac.authorization.k8s.io
  kind: ClusterRole
  name: ig-tpu-agent
subjects:
- kind: ServiceAccount
  name: ig-tpu-agent
  namespace: {namespace}
---
apiVersion: apps/v1
kind: DaemonSet
metadata:
  name: ig-tpu-agent
  namespace: {namespace}
spec:
  selector:
    matchLabels: {{k8s-app: ig-tpu-agent}}
  template:
    metadata:
      labels: {{k8s-app: ig-tpu-agent}}
    spec:
      serviceAccountName: ig-tpu-agent
      hostPID: true
      hostNetwork: true
      containers:
      - name: agent
        image: {image}
        command: [python, -m, inspektor_gadget_tpu.agent.main, serve,
                  --listen, "tcp://0.0.0.0:50051",
                  --node-name, "$(NODE_NAME)"]
        env:
        - name: NODE_NAME
          valueFrom: {{fieldRef: {{fieldPath: spec.nodeName}}}}
        securityContext:
          capabilities: {{add: [NET_RAW, NET_ADMIN, SYS_PTRACE]}}
        resources:
          limits:
            {tpu_resource}: {tpus_per_node}
        volumeMounts:
        - {{name: proc, mountPath: /host/proc, readOnly: true}}
        - {{name: run, mountPath: /run}}
      volumes:
      - {{name: proc, hostPath: {{path: /proc}}}}
      - {{name: run, hostPath: {{path: /run}}}}
"""


STATE_FILE = "/tmp/ig-tpu-agents.json"


def _alive(pid: int) -> bool:
    import os
    try:
        os.kill(pid, 0)
        return True
    except OSError:
        return False


# what a host with TPU chips shows in /dev: accel nodes (v2-v4) or
# numbered vfio groups (v5e and later)
TPU_DEVICE_GLOBS = ("/dev/accel[0-9]*", "/dev/vfio/[0-9]*")


def local_agent_platforms(n: int) -> list[str]:
    """The explicit `--platform` of each agent of a local fleet. A chip
    belongs to ONE process, so at most one agent may ask for the
    accelerator: node-0 gets `tpu` when this host shows TPU device nodes
    (TPU_DEVICE_GLOBS — looking initialises nothing, so the deploying
    process never holds the chip) and JAX is not pinned elsewhere from
    outside; every other agent is pinned to `cpu`. An agent told `tpu`
    that finds none exits 1 and says so in its log."""
    import glob
    import os

    pinned = os.environ.get("JAX_PLATFORMS", "")
    chips = any(glob.glob(g) for g in TPU_DEVICE_GLOBS)
    first = "tpu" if chips and (not pinned or "tpu" in pinned) else "cpu"
    return [first] + ["cpu"] * (n - 1)


def local_agent_argv(node: str, target: str, platform: str) -> list[str]:
    import sys
    return [sys.executable, "-m", "inspektor_gadget_tpu.agent.main", "serve",
            "--listen", target, "--node-name", node, "--platform", platform]


def deploy_local(n: int, base_port: int = 50151) -> dict[str, str]:
    """Start n local agent daemons (subprocesses); returns node→target.
    Each agent's output goes to <log dir>/<node>.log, the log dir a fresh
    private directory under the temp dir (TMPDIR); it and each agent's
    platform (see local_agent_platforms) land in the state file."""
    import json
    import os
    import subprocess
    import tempfile

    # refuse to orphan a live fleet: a second deploy would fail port-bind
    # and overwrite the only record of the running agents
    try:
        with open(STATE_FILE) as f:
            old = json.load(f)
        if any(_alive(p) for p in old.get("pids", {}).values()):
            raise RuntimeError(
                "a local agent fleet is already running — "
                "`ig-tpu undeploy` it first")
    except (OSError, ValueError):
        pass

    targets = {}
    pids = {}
    platforms = {}
    # mkdtemp: mode 0700 and a name nobody could plant beforehand; the
    # logs inside are created exclusively and never through a symlink
    log_dir = tempfile.mkdtemp(prefix="ig-tpu-agents-")
    for i, platform in enumerate(local_agent_platforms(n)):
        node, target = f"node-{i}", f"127.0.0.1:{base_port + i}"
        fd = os.open(os.path.join(log_dir, f"{node}.log"),
                     os.O_WRONLY | os.O_CREAT | os.O_EXCL | os.O_NOFOLLOW,
                     0o600)
        try:
            p = subprocess.Popen(local_agent_argv(node, target, platform),
                                 stdout=fd, stderr=subprocess.STDOUT)
        finally:
            os.close(fd)
        targets[node] = target
        pids[node] = p.pid
        platforms[node] = platform
    with open(STATE_FILE, "w") as f:
        json.dump({"targets": targets, "pids": pids,
                   "platforms": platforms, "log_dir": log_dir}, f)
    return targets


def _local_state(field: str, default):
    import json
    try:
        with open(STATE_FILE) as f:
            return json.load(f)[field]
    except (OSError, ValueError, KeyError):
        return default


def local_platforms() -> dict[str, str]:
    return _local_state("platforms", {})


def local_log_dir() -> str:
    return _local_state("log_dir", "")


def local_targets() -> dict[str, str]:
    return _local_state("targets", {})


def undeploy_local() -> list[str]:
    """Stop agents started by deploy_local (ref: undeploy.go removes the
    DaemonSet + RBAC; here we terminate the local fleet)."""
    import json
    import os
    import signal as _signal

    stopped = []
    try:
        with open(STATE_FILE) as f:
            state = json.load(f)
    except (OSError, ValueError):
        return stopped
    for node, pid in state.get("pids", {}).items():
        try:
            os.kill(pid, _signal.SIGTERM)
            stopped.append(node)
        except OSError:  # dead pid, or recycled pid owned by someone else
            pass
    try:
        os.unlink(STATE_FILE)
    except OSError:
        pass
    return stopped


def render_undeploy(namespace: str = NAMESPACE) -> str:
    """Deletion list for kubectl delete -f (undeploy.go:1-254 analogue)."""
    return (
        f"# kubectl delete -f - <<EOF\n"
        f"apiVersion: v1\nkind: Namespace\nmetadata:\n  name: {namespace}\n"
        f"---\napiVersion: rbac.authorization.k8s.io/v1\nkind: ClusterRole\n"
        f"metadata:\n  name: ig-tpu-agent\n"
        f"---\napiVersion: rbac.authorization.k8s.io/v1\n"
        f"kind: ClusterRoleBinding\nmetadata:\n  name: ig-tpu-agent\n"
        f"# EOF\n"
    )
