"""Per-gadget behavior tests (model: the reference's gadget unit tests +
integration matchers, SURVEY §4)."""

import json
import os
import time

import pytest

import inspektor_gadget_tpu.all_gadgets  # noqa: F401
from inspektor_gadget_tpu.gadgets import GadgetContext, get, get_all
from inspektor_gadget_tpu.runtime import LocalRuntime


def run_gadget(category, name, timeout=0.6, param_overrides=None,
               collect_events=False, collect_arrays=False):
    desc = get(category, name)
    params = desc.params().to_params()
    if "source" in params:
        params.set("source", "pysynthetic")
        params.set("rate", "50000")
    for k, v in (param_overrides or {}).items():
        params.set(k, v)
    ctx = GadgetContext(desc, gadget_params=params, timeout=timeout)
    events, arrays = [], []
    result = LocalRuntime().run_gadget(
        ctx,
        on_event=events.append if collect_events else None,
        on_event_array=arrays.append if collect_arrays else None,
    )
    assert not result.errors(), result.errors()
    return result.first(), events, arrays


def test_all_expected_gadgets_registered():
    have = {(d.category, d.name) for d in get_all()}
    want = {
        ("trace", "exec"), ("trace", "open"), ("trace", "tcp"),
        ("trace", "tcpconnect"), ("trace", "bind"), ("trace", "dns"),
        ("trace", "sni"), ("trace", "network"), ("trace", "mount"),
        ("trace", "signal"), ("trace", "oomkill"), ("trace", "capabilities"),
        ("trace", "fsslower"),
        ("top", "file"), ("top", "tcp"), ("top", "block-io"), ("top", "sketch"),
        ("snapshot", "process"), ("snapshot", "socket"),
        ("profile", "cpu"), ("profile", "block-io"),
        ("audit", "seccomp"),
        ("advise", "seccomp-profile"), ("advise", "network-policy"),
        ("traceloop", "traceloop"),
    }
    missing = want - have
    assert not missing, f"missing gadgets: {missing}"


@pytest.mark.parametrize("name", ["open", "mount", "signal", "oomkill",
                                  "capabilities", "bind", "fsslower", "dns",
                                  "sni", "network"])
def test_trace_gadgets_stream_events(name):
    _, events, _ = run_gadget("trace", name, collect_events=True)
    assert len(events) > 10
    ev = events[0]
    assert ev.timestamp > 0


def test_audit_seccomp_decodes_syscalls():
    # synthetic rows are explicitly labeled SYNTH so fabricated decode can
    # never be mistaken for a captured seccomp outcome
    _, events, _ = run_gadget("audit", "seccomp", collect_events=True)
    assert events
    assert all(e.code == "SYNTH" for e in events[:20] if e is not None)


def test_snapshot_process_lists_self():
    import os
    result, _, _ = run_gadget("snapshot", "process")
    # ctx.result carries the row list; bytes result is the rendered table
    assert result and b"COMM" in result
    assert str(os.getpid()).encode() in result or b"python" in result


def test_snapshot_socket_parses_procnet():
    result, _, _ = run_gadget("snapshot", "socket")
    assert result and b"PROTOCOL" in result


def test_top_file_emits_arrays():
    _, _, arrays = run_gadget("top", "file", timeout=2.5,
                              param_overrides={"interval": "1s"},
                              collect_arrays=True)
    assert arrays  # at least one tick (rows may be empty on idle systems)


def test_profile_blockio_histogram_renders():
    result, _, _ = run_gadget("profile", "block-io", timeout=0.8)
    assert b"usecs" in result and b"distribution" in result
    # the output names its window so degraded data is never mistaken
    # for the per-IO distribution
    assert b"source:" in result


def test_profile_blockio_diskstats_flavour_labeled():
    result, _, _ = run_gadget("profile", "block-io", timeout=0.6,
                              param_overrides={"window": "diskstats"})
    assert b"degraded" in result


def test_profile_blockio_per_io_distribution():
    """With the tracefs window, every IO lands in its own latency bucket —
    a real distribution, not a windowed average (biolatency.bpf.c parity)."""
    import subprocess
    import threading

    from inspektor_gadget_tpu.sources.bridge import blktrace_supported
    if not blktrace_supported() or os.geteuid() != 0:
        pytest.skip("tracefs block events unavailable")

    def io_load():
        time.sleep(0.5)
        for _ in range(3):
            subprocess.run(
                ["dd", "if=/dev/zero", "of=/tmp/ig_blk_g", "bs=4096",
                 "count=64", "oflag=direct"],
                stderr=subprocess.DEVNULL, check=False)

    t = threading.Thread(target=io_load)
    t.start()
    try:
        result, _, _ = run_gadget(
            "profile", "block-io", timeout=3.0,
            param_overrides={"window": "blktrace"})
    finally:
        t.join()
    assert b"per-IO" in result
    # at least ~100 IOs counted individually across the histogram
    counts = [int(line.split(":")[1].split("|")[0])
              for line in result.decode().splitlines()
              if "->" in line and ":" in line]
    assert sum(counts) >= 100, result.decode()


def test_trace_mount_per_container_mntns_attach():
    """Mounts inside a container's private mount ns are invisible to the
    host mountinfo; the Attacher path polls the container's own
    /proc/<pid>/mountinfo (mountsnoop.bpf.c parity: system-wide
    tracepoints see every mount ns)."""
    import shutil
    import subprocess
    import threading

    from inspektor_gadget_tpu.sources.bridge import native_available
    if (not native_available() or os.geteuid() != 0
            or not shutil.which("unshare")):
        pytest.skip("netns tooling unavailable")

    child = subprocess.Popen(
        ["unshare", "-m", "bash", "-c",
         "sleep 1.2; for i in 1 2 3; do mount -t tmpfs igtmp_$i /mnt; "
         "sleep 0.4; umount /mnt; sleep 0.3; done; sleep 5"])
    try:
        time.sleep(0.3)
        desc = get("trace", "mount")
        ctx = GadgetContext(desc, gadget_params=desc.params().to_params(),
                            timeout=5.0)
        g = desc.new_instance(ctx)

        class _C:
            id = "mnt-probe"
            pid = child.pid
        g.attach_container(_C())
        events = []
        g.set_event_handler(events.append)
        threading.Thread(target=ctx.wait_for_timeout_or_done,
                         daemon=True).start()
        g.run(ctx)
    finally:
        child.kill()
        child.wait()
    mine = [(e.operation, e.source) for e in events
            if e is not None and "igtmp" in e.source]
    assert any(op == "mount" for op, _ in mine), mine
    assert any(op == "umount" for op, _ in mine), mine


def test_trace_exec_args_and_ppid():
    """The native exec window carries execsnoop's headline columns: ARGS
    (full argv) and PPID, enriched at capture time (tracer.go:169-181
    parses the same buffer from the BPF event)."""
    import subprocess
    import threading

    from inspektor_gadget_tpu.sources.bridge import native_available
    if not native_available() or os.geteuid() != 0:
        pytest.skip("native exec window unavailable")

    stop = threading.Event()

    def workload():
        time.sleep(0.6)
        while not stop.is_set():
            # the unusual duration doubles as the argv marker; the 130ms
            # lifetime guarantees the capture thread's /proc/cmdline read
            # wins the race (an instantly-exiting `true` can lose it)
            subprocess.run(["sleep", "0.137"], check=False)
            stop.wait(0.1)

    t = threading.Thread(target=workload)
    t.start()
    try:
        _, events, _ = run_gadget(
            "trace", "exec", timeout=3.0,
            param_overrides={"source": "native"}, collect_events=True)
    finally:
        stop.set()
        t.join()
    mine = [e for e in events
            if e is not None and e.args == "sleep 0.137"]
    assert mine, [e.args for e in events if e is not None and e.args][:10]
    assert any(e.ppid == os.getpid() for e in mine)


def _audit_window_available():
    from inspektor_gadget_tpu.sources.bridge import audit_supported
    return audit_supported()


def test_trace_capabilities_host_wide_denials():
    """With no target, trace/capabilities observes real host-wide denials
    via the kernel audit stream (capable.bpf.c:1-250 parity: system-wide
    scope, denial verdicts from failed EPERM/EACCES syscalls)."""
    import subprocess
    import threading

    if not _audit_window_available() or os.geteuid() != 0:
        pytest.skip("audit window unavailable")

    target = "/tmp/ig_cap_host_t"
    open(target, "w").close()
    stop = threading.Event()

    def trigger():
        # rule install needs a few netlink round-trips; keep triggering
        # cheap EPERM chowns (setpriv execs chown directly — no interpreter
        # startup) across the whole gadget window so load can't starve it
        time.sleep(0.5)
        while not stop.is_set():
            subprocess.run(
                ["setpriv", "--reuid", "65534", "--clear-groups",
                 "chown", "0:0", target],
                check=False, stderr=subprocess.DEVNULL)
            stop.wait(0.25)

    t = threading.Thread(target=trigger)
    t.start()
    try:
        _, events, _ = run_gadget(
            "trace", "capabilities", timeout=4.0,
            param_overrides={"source": "auto"}, collect_events=True)
    finally:
        stop.set()
        t.join()
        os.unlink(target)
    denials = [e for e in events
               if e is not None and e.cap == "CHOWN" and e.verdict == "deny"]
    assert denials, [getattr(e, "cap", None) for e in events][:10]
    assert all(e.pid > 0 for e in denials)


def test_audit_seccomp_host_wide_kills():
    """With no target, audit/seccomp reports real host-wide seccomp kills
    via AUDIT_SECCOMP records (audit-seccomp.bpf.c:1-65 parity)."""
    import subprocess
    import threading

    if not _audit_window_available() or os.geteuid() != 0:
        pytest.skip("audit window unavailable")

    # a tiny compiled trigger avoids interpreter startup latency: under
    # full-suite load a `python -c` child can take >1s, sliding every
    # trigger past the gadget window
    helper = "/tmp/ig_seccomp_trigger"
    if not os.path.exists(helper):
        src = "/tmp/ig_seccomp_trigger.c"
        with open(src, "w") as f:
            f.write("#include <sys/prctl.h>\n#include <unistd.h>\n"
                    "int main(){prctl(22,1,0,0,0);return getpid();}\n")
        subprocess.run(["g++", "-O1", "-o", helper, src], check=True)

    stop = threading.Event()

    def trigger():
        time.sleep(0.5)
        while not stop.is_set():
            subprocess.run([helper], check=False)  # SIGKILL + audit record
            stop.wait(0.25)

    t = threading.Thread(target=trigger)
    t.start()
    try:
        _, events, _ = run_gadget(
            "audit", "seccomp", timeout=4.0,
            param_overrides={"source": "auto"}, collect_events=True)
    finally:
        stop.set()
        t.join()
    kills = [e for e in events
             if e is not None and e.code in ("KILL_THREAD", "KILL_PROCESS")]
    assert kills, [getattr(e, "code", None) for e in events][:10]
    assert any(e.syscall == "getpid" for e in kills)


def test_trace_tcp_event_driven_state_transitions():
    """With the inet_sock_set_state window, trace/tcp reports real
    connect/accept/close events with tuple and pid attribution — no scan
    window (tcptracer.bpf.c:1-375 parity)."""
    import socket
    import threading

    from inspektor_gadget_tpu.sources.bridge import sockstate_supported
    if not sockstate_supported() or os.geteuid() != 0:
        pytest.skip("inet_sock_set_state window unavailable")

    port_box = {}
    stop = threading.Event()

    def workload():
        time.sleep(0.8)
        ls = socket.socket()
        ls.bind(("127.0.0.1", 0))
        ls.listen(4)
        port_box["port"] = ls.getsockname()[1]
        def srv():
            while not stop.is_set():
                try:
                    ls.settimeout(0.5)
                    conn, _ = ls.accept()
                    conn.close()
                except OSError:
                    pass
        st = threading.Thread(target=srv)
        st.start()
        while not stop.is_set():
            try:
                cs = socket.create_connection(
                    ("127.0.0.1", port_box["port"]), timeout=1.0)
                cs.close()
            except OSError:
                pass
            stop.wait(0.25)
        st.join()
        ls.close()

    t = threading.Thread(target=workload)
    t.start()
    try:
        _, events, _ = run_gadget(
            "trace", "tcp", timeout=4.0,
            param_overrides={"source": "native"}, collect_events=True)
        # connect-only view against the same live workload: the kind
        # filter must drop the accept/close transitions
        _, cevents, _ = run_gadget(
            "trace", "tcpconnect", timeout=2.0,
            param_overrides={"source": "native"}, collect_events=True)
    finally:
        stop.set()
        t.join()
    port = port_box.get("port")
    mine = [e for e in events
            if e is not None and port in (e.sport, e.dport)]
    ops = {e.operation for e in mine}
    assert {"connect", "accept", "close"} <= ops, (port, ops)
    connects = [e for e in mine if e.operation == "connect"]
    # kubeipresolver may suffix a label onto addresses ("127.0.0.1 (host)")
    assert all(e.daddr.startswith("127.0.0.1") and e.dport == port
               for e in connects)
    assert any(e.pid > 0 and e.comm for e in connects)
    cmine = [e for e in cevents if e is not None]
    assert cmine and all(e.operation == "connect" for e in cmine)


def test_trace_signal_host_wide_tracepoint():
    """With the signal_generate window, trace/signal reports every signal
    host-wide with sender and target (sigsnoop.bpf.c:1-175 parity) — not
    just fatal exits."""
    import signal as sig_mod
    import subprocess
    import threading

    from inspektor_gadget_tpu.sources.bridge import sigtrace_supported
    if not sigtrace_supported() or os.geteuid() != 0:
        pytest.skip("signal_generate window unavailable")

    stop = threading.Event()
    victim = subprocess.Popen(["sleep", "30"])

    def trigger():
        time.sleep(0.8)
        while not stop.is_set():
            os.kill(victim.pid, sig_mod.SIGUSR2)  # non-fatal... for sleep
            stop.wait(0.25)

    t = threading.Thread(target=trigger)
    t.start()
    try:
        _, events, _ = run_gadget(
            "trace", "signal", timeout=3.0,
            param_overrides={"source": "native"}, collect_events=True)
    finally:
        stop.set()
        t.join()
        victim.kill()
        victim.wait()
    # SIGUSR2 kills sleep (default action term) — either way the GENERATE
    # event must carry sender (this process) and target (the sleep pid)
    mine = [e for e in events
            if e is not None and e.tpid == victim.pid and e.origin == "sent"]
    assert mine, [(getattr(e, "tpid", None), getattr(e, "origin", None))
                  for e in events][:10]
    # the sender pid in the trace line is the sending THREAD's tid (the
    # trigger runs in a pytest worker thread), so assert attribution
    # exists rather than equality with the process pid
    assert any(e.pid > 0 and e.comm for e in mine)


def test_trace_fsslower_host_wide():
    """With no target, trace/fsslower observes real host-wide slow fs ops
    via filtered raw_syscalls tracepoints (fsslower.bpf.c:1-239 parity:
    system-wide entry/exit latency above a threshold)."""
    import subprocess
    import threading

    from inspektor_gadget_tpu.sources.bridge import fstrace_supported
    if not fstrace_supported() or os.geteuid() != 0:
        pytest.skip("raw_syscalls window unavailable")

    stop = threading.Event()
    fifo = "/tmp/ig_fsslow_fifo"
    try:
        os.unlink(fifo)
    except OSError:
        pass
    os.mkfifo(fifo)

    def slow_io():
        # a fifo whose writer delays guarantees a >=50ms blocking read on
        # ANY filesystem (dd O_DIRECT tricks fail with EINVAL on tmpfs)
        time.sleep(0.5)
        while not stop.is_set():
            # writer opens the fifo immediately (so the reader's open
            # returns fast) but delays each WRITE — the slow ops are the
            # reads, and the second blocking read keeps dd alive while the
            # first read's exit record resolves its fd path via /proc
            subprocess.run(
                ["sh", "-c",
                 f"( exec 3>{fifo}; sleep 0.08; printf 12345678 >&3; "
                 f"sleep 0.4; printf 12345678 >&3 ) & "
                 f"dd if={fifo} of=/dev/null bs=8 count=2; wait"],
                stderr=subprocess.DEVNULL, check=False)
            stop.wait(0.15)

    t = threading.Thread(target=slow_io)
    t.start()
    try:
        _, events, _ = run_gadget(
            "trace", "fsslower", timeout=4.0,
            param_overrides={"source": "auto", "min-latency": "1"},
            collect_events=True)
    finally:
        stop.set()
        t.join()
        try:
            os.unlink(fifo)
        except OSError:
            pass
    slow = [e for e in events if e is not None and e.latency_us >= 1000]
    assert slow, [getattr(e, "latency_us", None) for e in events][:10]
    dd_rows = [e for e in slow if e.comm == "dd" and e.op == "read"]
    assert dd_rows, [(e.comm, e.op) for e in slow][:10]
    assert any(e.file == fifo for e in dd_rows)


def test_top_file_per_file_rows_under_dd_workload():
    """With the fanotify window, top/file's unit of account is the FILE —
    rows carry real filenames per (pid, file) (filetop.bpf.c:1-108 parity:
    per-(pid,file) stats map → fanotify open/modify aggregation)."""
    import subprocess
    import threading

    from inspektor_gadget_tpu.gadgets.top.file import (
        _fanotify_window_available,
    )
    if not _fanotify_window_available() or os.geteuid() != 0:
        pytest.skip("fanotify window unavailable")

    target = "/tmp/ig_filetop_target"

    done = threading.Event()

    def io_load():
        # write until the run ends, not on a fixed schedule: under load the
        # fanotify marks may go live later than any fixed head start
        # dd is short-lived (it may exit before the capture thread reads
        # its /proc identity); this process also writes, and stays
        while not done.is_set():
            subprocess.run(
                ["dd", "if=/dev/zero", f"of={target}", "bs=4096",
                 "count=200", "conv=notrunc"],
                stderr=subprocess.DEVNULL, check=False)
            with open(target, "r+b") as f:
                f.write(b"x" * 4096)
            done.wait(0.3)

    t = threading.Thread(target=io_load)
    t.start()
    try:
        _, _, arrays = run_gadget(
            "top", "file", timeout=3.0,
            param_overrides={"interval": "1s", "window": "fanotify"},
            collect_arrays=True)
    finally:
        done.set()
        t.join()
        try:
            os.unlink(target)
        except OSError:
            pass
    rows = [r for tick in arrays for r in tick]
    mine = [r for r in rows if r.file == target]
    assert mine, f"no per-file rows for {target}: " \
                 f"{sorted({r.file for r in rows})[:15]}"
    assert sum(r.writes for r in mine) > 0
    # a short-lived dd may exit before the capture thread reads its /proc
    # identity, so comm can be empty on a straggler row — but at least one
    # row must be fully identified
    assert any(r.pid > 0 and r.comm for r in mine)


def test_top_file_procio_flavour_still_works():
    _, _, arrays = run_gadget("top", "file", timeout=2.2,
                              param_overrides={"interval": "1s",
                                               "window": "procio"},
                              collect_arrays=True)
    assert arrays  # ticks emitted; rows may be empty on an idle host


def test_trace_open_per_container_mount_attach():
    """Opens on a container's private mounts are invisible to the host "/"
    mount mark; the Attacher path marks the container's root mount via
    /proc/<pid>/root, capturing them with resolved paths."""
    import shutil
    import subprocess
    import threading

    from inspektor_gadget_tpu.gadgets.top.file import (
        _fanotify_window_available,
    )
    if (not _fanotify_window_available() or os.geteuid() != 0
            or not shutil.which("unshare")):
        pytest.skip("fanotify/netns tooling unavailable")

    # writes land on BOTH the container's root mount (a private clone the
    # host "/" mark does not see) and a volume-style tmpfs submount, which
    # the attach covers via the container's mount table
    child = subprocess.Popen(
        ["unshare", "-m", "bash", "-c",
         "mount -t tmpfs igvol /mnt; sleep 0.8; "
         "for i in $(seq 1 50); do echo hi > /ig_attach_open_$i; "
         "echo hi > /mnt/ig_attach_vol_$i; "
         "sleep 0.1; done; rm -f /ig_attach_open_*"])
    try:
        time.sleep(0.8)
        desc = get("trace", "open")
        ctx = GadgetContext(desc, gadget_params=desc.params().to_params(),
                            timeout=4.0)
        g = desc.new_instance(ctx)

        class _C:
            id = "open-mnt-probe"
            pid = child.pid
        g.attach_container(_C())
        events = []
        g.set_event_handler(events.append)
        threading.Thread(target=ctx.wait_for_timeout_or_done,
                         daemon=True).start()
        g.run(ctx)
    finally:
        child.kill()
        child.wait()
        import glob
        for leftover in glob.glob("/ig_attach_open_*"):
            try:
                os.unlink(leftover)
            except OSError:
                pass
    mine = [e for e in events
            if e is not None and "ig_attach_open_" in e.path]
    assert mine, sorted({e.path for e in events if e is not None})[:10]
    assert any(e.op == "write" and e.pid > 0 for e in mine)
    # volume-style submounts are covered too (marked from the container's
    # own mount table)
    vol = [e for e in events
           if e is not None and "ig_attach_vol_" in e.path]
    assert vol, sorted({e.path for e in events if e is not None})[:10]


def test_trace_open_covers_post_attach_mounts():
    """A tmpfs mounted AFTER attach is marked live by the source's remark
    loop polling the container's mountinfo (VERDICT r4 item 6; ref:
    opensnoop.bpf.c sees every open regardless of when the mount
    appeared)."""
    import shutil
    import subprocess
    import threading

    from inspektor_gadget_tpu.gadgets.top.file import (
        _fanotify_window_available,
    )
    if (not _fanotify_window_available() or os.geteuid() != 0
            or not shutil.which("unshare")):
        pytest.skip("fanotify/netns tooling unavailable")

    child = subprocess.Popen(
        ["unshare", "-m", "bash", "-c",
         "sleep 1.5; mount -t tmpfs igpost /mnt; "
         "for i in $(seq 1 40); do echo hi > /mnt/ig_post_mount_$i; "
         "sleep 0.1; done; sleep 3"])
    try:
        time.sleep(0.3)  # attach BEFORE the mount exists
        desc = get("trace", "open")
        ctx = GadgetContext(desc, gadget_params=desc.params().to_params(),
                            timeout=6.0)
        g = desc.new_instance(ctx)

        class _C:
            id = "post-mount-probe"
            pid = child.pid
        g.attach_container(_C())
        events = []
        g.set_event_handler(events.append)
        threading.Thread(target=ctx.wait_for_timeout_or_done,
                         daemon=True).start()
        g.run(ctx)
    finally:
        child.kill()
        child.wait()
    mine = [e for e in events
            if e is not None and "ig_post_mount_" in e.path]
    assert mine, sorted({e.path for e in events if e is not None})[:10]


def test_snapshot_socket_covers_container_netns():
    """snapshot/socket lists sockets of tracked containers' private netns
    too (the reference iterates per container netns), via each pid's
    /proc/<pid>/net view — with container identity on the rows."""
    import shutil
    import subprocess
    import sys

    if (os.geteuid() != 0 or not shutil.which("unshare")
            or not shutil.which("ip")):
        pytest.skip("netns tooling unavailable")

    from inspektor_gadget_tpu.containers import Container
    from inspektor_gadget_tpu.operators.operators import ensure_initialized

    # -S skips site processing: the listener must be up quickly
    child = subprocess.Popen(
        ["unshare", "-n", "bash", "-c",
         f"ip link set lo up && {sys.executable} -S -c \"\n"
         "import socket, time\n"
         "ls = socket.socket(); ls.bind(('127.0.0.1', 46123)); ls.listen(1)\n"
         "time.sleep(20)\n"
         "\""])
    lm = ensure_initialized("localmanager")
    cid = "netns-snap-probe"
    try:
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:  # wait for the bind, not a guess
            try:
                if "B42B" in open(f"/proc/{child.pid}/net/tcp").read():
                    break
            except OSError:
                pass
            time.sleep(0.2)
        lm.cc.add_container(Container(id=cid, name="snap-probe",
                                      pid=child.pid))
        _, _, arrays = run_gadget("snapshot", "socket", timeout=0.5,
                                  param_overrides={"proto": "tcp"},
                                  collect_arrays=True)
    finally:
        lm.cc.remove_container(cid)
        child.kill()
        child.wait()
    rows = [r for tick in arrays for r in tick]
    mine = [r for r in rows if r.localport == 46123]
    assert mine, f"container-netns LISTEN socket missing " \
                 f"({len(rows)} rows total)"
    assert any(r.container == "snap-probe" and r.status == "LISTEN"
               and r.netnsid > 0 for r in mine)


def test_trace_dns_per_netns_container_attach():
    """A DNS query inside a container's private netns is invisible to the
    host-netns sniffer; the Attacher path opens one sniffer per container
    netns (networktracer/tracer.go:54-220 parity: one refcounted
    attachment per netns)."""
    import shutil
    import subprocess
    import sys
    import threading

    from inspektor_gadget_tpu.sources.bridge import native_available
    if (not native_available() or os.geteuid() != 0
            or not shutil.which("unshare") or not shutil.which("ip")):
        pytest.skip("netns tooling unavailable")

    child = subprocess.Popen(
        ["unshare", "-n", "bash", "-c",
         f"ip link set lo up && {sys.executable} -c \"\n"
         "import socket, struct, time\n"
         "time.sleep(2.0)\n"
         "q = struct.pack('>HHHHHH', 0x1234, 0x0100, 1, 0, 0, 0)\n"
         "q += b'\\x07netnsgd\\x07example\\x03com\\x00'"
         " + struct.pack('>HH', 1, 1)\n"
         "s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)\n"
         "for _ in range(30):\n"
         "    s.sendto(q, ('127.0.0.1', 53)); time.sleep(0.15)\n"
         "\""])
    try:
        time.sleep(0.5)
        desc = get("trace", "dns")
        params = desc.params().to_params()
        ctx = GadgetContext(desc, gadget_params=params, timeout=6.0)
        g = desc.new_instance(ctx)

        class _C:
            id = "dns-netns"
            pid = child.pid
        g.attach_container(_C())
        events = []
        g.set_event_handler(events.append)
        threading.Thread(target=ctx.wait_for_timeout_or_done,
                         daemon=True).start()
        g.run(ctx)
    finally:
        child.kill()
        child.wait()
    names = {e.name for e in events if e is not None}
    assert any("netnsgd" in n for n in names), sorted(names)[:10]


def test_top_tcp_per_netns_container_attach():
    """A container with a private netns is invisible to the host-netns
    sock_diag dump; the Attacher path spawns a per-container byte source
    whose capture thread setns()es into the container's netns (the
    per-netns flavour the docs promise)."""
    import shutil
    import subprocess

    from inspektor_gadget_tpu.sources.bridge import tcpinfo_supported
    if (not tcpinfo_supported() or os.geteuid() != 0
            or not shutil.which("unshare") or not shutil.which("ip")):
        pytest.skip("netns tooling or INET_DIAG_INFO unavailable")

    import sys
    child = subprocess.Popen(
        ["unshare", "-n", "bash", "-c",
         f"ip link set lo up && {sys.executable} -c \"\n"
         "import socket, threading, time\n"
         "ls = socket.socket(); ls.bind(('127.0.0.1', 41998)); ls.listen(1)\n"
         "def srv():\n"
         "    conn, _ = ls.accept()\n"
         "    while conn.recv(65536): pass\n"
         "t = threading.Thread(target=srv); t.start()\n"
         "time.sleep(2.5)\n"
         "cs = socket.create_connection(('127.0.0.1', 41998))\n"
         "for _ in range(48): cs.sendall(b'x'*65536); time.sleep(0.03)\n"
         "time.sleep(2.0); cs.close(); t.join()\n"
         "\""])
    try:
        time.sleep(1.0)
        desc = get("top", "tcp")
        params = desc.params().to_params()
        ctx = GadgetContext(desc, gadget_params=params, timeout=6.0)
        g = desc.new_instance(ctx)

        class _C:
            id = "netns-probe"
            pid = child.pid
        g.attach_container(_C())
        arrays = []
        g.set_event_handler_array(arrays.append)
        import threading
        threading.Thread(target=ctx.wait_for_timeout_or_done,
                         daemon=True).start()  # the runtime's timeout role
        g.run(ctx)
        rows = [r for tick in arrays for r in tick]
        mine = [r for r in rows if ":41998" in r.conn]
        assert mine, sorted({r.conn for r in rows})[:10]
        assert sum(r.sent for r in mine) > 1 << 20
    finally:
        child.kill()
        child.wait()


def test_top_tcp_real_bytes_under_live_workload():
    """With the INET_DIAG_INFO window, top/tcp reports real per-connection
    SENT/RECV byte counts (tcptop.bpf.c:1-133 parity: kprobe byte sums →
    sock_diag tcp_info counter deltas)."""
    import socket
    import threading

    from inspektor_gadget_tpu.sources.bridge import tcpinfo_supported
    if not tcpinfo_supported():
        pytest.skip("sock_diag INET_DIAG_INFO unavailable")

    total = {"recv": 0}
    ls = socket.socket()
    ls.bind(("127.0.0.1", 0))
    ls.listen(1)
    port = ls.getsockname()[1]
    stop = threading.Event()

    def server():
        conn, _ = ls.accept()
        while True:
            d = conn.recv(65536)
            if not d:
                break
            total["recv"] += len(d)
        conn.close()

    def client():
        cs = socket.create_connection(("127.0.0.1", port))
        chunk = b"x" * 65536
        # pace ~6 MB across the gadget run so multiple poll ticks observe
        # live deltas, and hold the socket open until the gadget is done
        # (a socket gone before the next dump loses its last delta)
        for _ in range(96):
            cs.sendall(chunk)
            time.sleep(0.02)
        stop.wait(timeout=5.0)
        cs.close()

    st = threading.Thread(target=server)
    ct = threading.Thread(target=client)
    st.start()
    ct.start()
    try:
        _, _, arrays = run_gadget(
            "top", "tcp", timeout=3.5,
            param_overrides={"interval": "1s", "source": "native"},
            collect_arrays=True)
    finally:
        stop.set()
        ct.join()
        st.join()
        ls.close()
    rows = [r for tick in arrays for r in tick]
    mine = [r for r in rows if f":{port}" in r.conn]
    assert mine, f"no rows for test connection on port {port}: " \
                 f"{[r.conn for r in rows][:10]}"
    sent = sum(r.sent for r in mine)
    recv = sum(r.recv for r in mine)
    # both directions of the loopback pair were live sockets; between them
    # the full transfer must be accounted (deltas, not fabrications)
    assert sent + recv >= total["recv"] > 1 << 20, (sent, recv, total)
    assert all(r.pid > 0 for r in mine)


def test_profile_blockio_quantiles_param():
    result, _, _ = run_gadget("profile", "block-io", timeout=0.8,
                              param_overrides={"quantiles": "true"})
    # quantile line appears whenever any IO was observed in the window
    if b"p50=" in result:
        assert b"ddsketch" in result and b"p99=" in result
    else:  # idle disk: histogram still renders, no quantile line
        assert b"distribution" in result


def test_profile_cpu_columns_and_folded():
    result, _, _ = run_gadget("profile", "cpu", timeout=0.7)
    assert b"SAMPLES" in result
    folded, _, _ = run_gadget("profile", "cpu", timeout=0.7,
                              param_overrides={"profile-output": "folded"})
    # folded lines end with a count
    line = folded.decode().strip().splitlines()[0]
    assert line.rsplit(" ", 1)[1].isdigit()


def test_advise_seccomp_profile_generates_oci_json():
    result, _, _ = run_gadget("advise", "seccomp-profile", timeout=0.8)
    profiles = json.loads(result)
    assert profiles
    prof = next(iter(profiles.values()))
    assert prof["defaultAction"] == "SCMP_ACT_ERRNO"
    names = prof["syscalls"][0]["names"]
    assert "execve" in names and prof["syscalls"][0]["action"] == "SCMP_ACT_ALLOW"


def test_advise_seccomp_profile_generates_cr_yaml():
    """--format cr renders SeccompProfile custom resources (ref:
    gadget-collection/gadgets/advise/seccomp/gadget.go:582)."""
    result, _, _ = run_gadget(
        "advise", "seccomp-profile", timeout=0.8,
        param_overrides={"format": "cr", "profile-name": "web"})
    text = result.decode()
    assert "kind: SeccompProfile" in text
    assert "security-profiles-operator.x-k8s.io/v1beta1" in text
    assert 'name: "web-' in text  # user-supplied names are quoted
    assert "defaultAction: SCMP_ACT_ERRNO" in text
    assert "- execve" in text
    # must parse as YAML when a parser is around (structure check)
    try:
        import yaml
    except ImportError:
        pass
    else:
        docs = list(yaml.safe_load_all(text))
        assert docs and docs[0]["kind"] == "SeccompProfile"
        assert "execve" in docs[0]["spec"]["syscalls"][0]["names"]


def test_advise_network_policy_generates_yaml():
    result, _, _ = run_gadget("advise", "network-policy", timeout=0.8)
    text = result.decode()
    assert "kind: NetworkPolicy" in text
    assert "policyTypes:" in text
    assert "port:" in text


def test_traceloop_retrospective_read():
    result, _, _ = run_gadget("traceloop", "traceloop", timeout=0.8)
    text = result.decode()
    assert "SYSCALL" in text
    assert len(text.splitlines()) > 5


def test_traceloop_ring_overwrites_oldest():
    from inspektor_gadget_tpu.gadgets.traceloop.traceloop import Traceloop
    desc = get("traceloop", "traceloop")
    params = desc.params().to_params()
    params.set("source", "pysynthetic")
    params.set("ring-size", "16")
    ctx = GadgetContext(desc, gadget_params=params, timeout=0.5)
    g = desc.new_instance(ctx)
    import numpy as np
    from inspektor_gadget_tpu.sources import EventBatch
    b = EventBatch.alloc(100)
    b.cols["mntns"][:] = 42
    b.cols["ts"][:] = np.arange(100)
    b.cols["aux2"][:] = np.arange(100)
    b.count = 100
    g.process_batch(b)
    records = g.read(42)
    assert len(records) == 16  # overwrote the oldest 84
    assert records[-1].timestamp == 99
