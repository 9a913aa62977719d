"""Real-capture tests: each gadget triggers a real system action and
asserts the captured event — the reference's kernel-real tracer-test
pattern (pkg/gadgets/trace/exec/tracer/tracer_test.go:35-301: install,
trigger, assert) applied to every formerly-synthetic gadget.
"""

from __future__ import annotations

import os
import socket
import subprocess
import time

import numpy as np
import pytest

from inspektor_gadget_tpu.sources import (
    NativeCapture, native_available, make_cfg,
    SRC_FANOTIFY_OPEN, SRC_MOUNTINFO, SRC_SOCK_DIAG, SRC_KMSG_OOM,
    SRC_PTRACE, SRC_FANOTIFY_RUNC, SRC_PERF_CPU, SRC_SYNTH_EXEC,
)

needs_native = pytest.mark.skipif(not native_available(), reason="no native lib")
needs_root = pytest.mark.skipif(os.geteuid() != 0, reason="needs root")

EV_OPEN, EV_BIND, EV_SIGNAL, EV_MOUNT, EV_OOMKILL = 3, 8, 9, 10, 11
EV_CAPABILITY, EV_FSSLOWER, EV_SYSCALL, EV_PERF, EV_CONTAINER = 12, 13, 18, 19, 20


def drain(src, want, timeout=4.0, kinds=None):
    """Pop until `want(rows) -> bool` is satisfied; returns collected rows
    as (kind, key_hash, aux1, aux2, pid, ppid, mntns, comm) tuples."""
    rows = []
    deadline = time.time() + timeout
    while time.time() < deadline:
        b = src.pop()
        c = b.cols
        for i in range(b.count):
            if kinds is not None and int(c["kind"][i]) not in kinds:
                continue
            rows.append((int(c["kind"][i]), int(c["key_hash"][i]),
                         int(c["aux1"][i]), int(c["aux2"][i]),
                         int(c["pid"][i]), int(c["ppid"][i]),
                         int(c["mntns"][i]), b.comm_str(i)))
        if want(rows):
            return rows
        time.sleep(0.05)
    return rows


# ---------------------------------------------------------------------------
# trace/open — fanotify mount mark sees a real file open with its path
# ---------------------------------------------------------------------------

@needs_native
@needs_root
def test_open_sees_real_file_access():
    src = NativeCapture(SRC_FANOTIFY_OPEN, cfg=make_cfg(paths="/tmp"),
                        ring_pow2=14)
    with src:
        time.sleep(0.3)
        subprocess.run(
            ["sh", "-c", "echo payload > /tmp/ig_open_probe && cat /tmp/ig_open_probe >/dev/null"],
            check=True)
        rows = drain(src, lambda r: any(
            src.vocab_lookup(a1) == "/tmp/ig_open_probe" for _, _, a1, *_ in r),
            kinds={EV_OPEN})
    hits = [r for r in rows if src.vocab_lookup(r[2]) == "/tmp/ig_open_probe"]
    assert hits, "fanotify did not surface the probe file open"
    # the writer (sh) produced a modify bit; the reader (cat) a plain open
    assert any(r[3] & 2 for r in hits) or any(r[3] & 1 for r in hits)
    assert all(r[4] != 0 for r in hits)  # pid attributed


# ---------------------------------------------------------------------------
# trace/mount — mountinfo diff sees a real tmpfs mount + umount
# ---------------------------------------------------------------------------

@needs_native
@needs_root
def test_mount_sees_real_tmpfs_mount():
    os.makedirs("/tmp/ig_mnt_probe", exist_ok=True)
    src = NativeCapture(SRC_MOUNTINFO, ring_pow2=12)
    with src:
        time.sleep(0.3)
        subprocess.run(["mount", "-t", "tmpfs", "ig_probe_fs", "/tmp/ig_mnt_probe"],
                       check=True)
        time.sleep(0.4)
        subprocess.run(["umount", "/tmp/ig_mnt_probe"], check=True)
        rows = drain(src, lambda r: len(r) >= 2, kinds={EV_MOUNT})
    payloads = [(src.vocab_lookup(kh).split("\x1f"), aux2)
                for _, kh, _, aux2, *_ in rows]
    mounts = [(p, a) for p, a in payloads if p[0] == "ig_probe_fs"]
    assert any(a & 1 == 0 for _, a in mounts), "mount event missing"
    assert any(a & 1 == 1 for _, a in mounts), "umount event missing"
    src_name, target, fstype = mounts[0][0]
    assert target == "/tmp/ig_mnt_probe" and fstype == "tmpfs"


# ---------------------------------------------------------------------------
# trace/bind — sock_diag diff sees real TCP listen + UDP bind with pid
# ---------------------------------------------------------------------------

@needs_native
def test_bind_sees_real_listeners():
    src = NativeCapture(SRC_SOCK_DIAG, cfg=make_cfg(interval_ms=30),
                        ring_pow2=12)
    with src:
        time.sleep(0.4)
        tcp = socket.socket()
        tcp.bind(("127.0.0.1", 48712))
        tcp.listen(1)
        udp = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        udp.bind(("0.0.0.0", 48713))
        rows = drain(src, lambda r: len({x[3] & 0xFFFF for x in r}
                                        & {48712, 48713}) == 2,
                     kinds={EV_BIND})
        tcp.close()
        udp.close()
    by_port = {r[3] & 0xFFFF: r for r in rows}
    assert 48712 in by_port and 48713 in by_port
    assert (by_port[48712][3] >> 16) & 0xFF == 6    # IPPROTO_TCP
    assert (by_port[48713][3] >> 16) & 0xFF == 17   # IPPROTO_UDP
    assert by_port[48712][4] == os.getpid()         # resolved to this process
    assert by_port[48712][7] == "python"[:7] or by_port[48712][7].startswith("py")


# ---------------------------------------------------------------------------
# trace/oomkill — kmsg parser decodes a real kernel-log OOM record
# (injected through /dev/kmsg so the test does not have to OOM the host;
#  the read path — kmsg stream, record framing, field parse — is the real one)
# ---------------------------------------------------------------------------

@needs_native
@needs_root
def test_oomkill_parses_kmsg_record():
    src = NativeCapture(SRC_KMSG_OOM, ring_pow2=12)
    with src:
        time.sleep(0.3)
        with open("/dev/kmsg", "w") as f:
            f.write("Out of memory: Killed process 31337 (ig_victim) "
                    "total-vm:204800kB, anon-rss:1024kB\n")
        rows = drain(src, lambda r: len(r) >= 1, kinds={EV_OOMKILL})
    assert rows, "kmsg OOM record not captured"
    kind, kh, pages, _aux2, pid, *_ = rows[0]
    assert pid == 31337
    assert src.vocab_lookup(kh) == "ig_victim"
    assert pages == 204800 // 4


# ---------------------------------------------------------------------------
# trace/signal — netlink exit records decode a real fatal signal
# ---------------------------------------------------------------------------

@needs_native
@needs_root
def test_signal_sees_real_fatal_signal():
    from inspektor_gadget_tpu.sources import SRC_PROC_EXEC
    src = NativeCapture(SRC_PROC_EXEC, ring_pow2=16)
    with src:
        time.sleep(0.3)
        # a child that kills itself with SIGUSR1 (fatal by default)
        subprocess.run(["sh", "-c", "kill -USR1 $$"], check=False)
        rows = drain(src, lambda r: any(x[3] == 10 for x in r),
                     kinds={EV_SIGNAL})
    fatal = [r for r in rows if r[3] == 10]
    assert fatal, "fatal SIGUSR1 not decoded from exit record"
    assert fatal[0][2] == 1  # origin: fatal


# ---------------------------------------------------------------------------
# ptrace stream — syscalls, signals (both sides), capabilities, fsslower
# ---------------------------------------------------------------------------

@needs_native
@needs_root
def test_ptrace_decodes_real_syscalls():
    src = NativeCapture(SRC_PTRACE, ring_pow2=16, cfg=make_cfg(
        cmd=["sh", "-c", "cat /etc/hostname >/dev/null"]))
    with src:
        rows = drain(src, lambda r: src.ptrace_exit_status() >= 0
                     and len(r) > 20, kinds={EV_SYSCALL}, timeout=6.0)
    lines = [src.vocab_lookup(kh) for _, kh, *_ in rows]
    execves = [l for l in lines if l.startswith("execve(")]
    opens = [l for l in lines if "/etc/hostname" in l]
    # sh's resolved path varies by host ($PATH walk: /bin/sh, /usr/bin/sh…)
    # — assert a successful execve of *some* sh, not a fixed location
    assert any(('/sh"' in l or '"sh"' in l) and l.endswith("= 0")
               for l in execves), execves
    assert any(l.startswith("openat(") and l.endswith("= 3") for l in opens), opens
    # nr/ret packed in aux2: every execve that succeeded has ret 0
    exec_rows = [r for r in rows if src.vocab_lookup(r[1]).startswith("execve(")
                 and src.vocab_lookup(r[1]).endswith("= 0")]
    assert all((r[3] & 0xFFFFFFFF) == 0 for r in exec_rows)


@needs_native
@needs_root
def test_ptrace_derives_capability_and_signal_events():
    open("/tmp/ig_cap_probe", "w").write("x")
    src = NativeCapture(SRC_PTRACE, ring_pow2=16, cfg=make_cfg(
        cmd=["sh", "-c", "chown 0:0 /tmp/ig_cap_probe; kill -TERM $$"]))
    with src:
        rows = drain(src, lambda r: src.ptrace_exit_status() >= 0,
                     kinds={EV_CAPABILITY, EV_SIGNAL}, timeout=6.0)
    caps = [r for r in rows if r[0] == EV_CAPABILITY]
    sigs = [r for r in rows if r[0] == EV_SIGNAL]
    assert any(r[3] == 0 and r[2] == 1 for r in caps), "CAP_CHOWN allow missing"
    assert any(r[3] == 5 for r in caps), "CAP_KILL missing"
    # sender (aux1=2) and delivery (aux1=0) sides of SIGTERM(15)
    assert any(r[3] == 15 and r[2] == 2 for r in sigs), "sender side missing"
    assert any(r[3] == 15 and r[2] == 0 for r in sigs), "delivery stop missing"


@needs_native
@needs_root
def test_ptrace_fsslower_measures_real_latency():
    src = NativeCapture(SRC_PTRACE, ring_pow2=16, cfg=make_cfg(
        cmd=["sh", "-c", "cat /etc/hostname >/dev/null"], min_lat_us=0))
    with src:
        rows = drain(src, lambda r: src.ptrace_exit_status() >= 0,
                     kinds={EV_FSSLOWER}, timeout=6.0)
    opens = [r for r in rows if (r[3] >> 32) == 3
             and src.vocab_lookup(r[1]) == "/etc/hostname"]
    assert opens, "open of /etc/hostname not measured"
    assert all(r[2] > 0 for r in opens)  # nonzero latency_us


# ---------------------------------------------------------------------------
# gadget-level: end-to-end through the framework with real capture
# ---------------------------------------------------------------------------

def _run_gadget(category, name, flags, trigger=None, timeout=4.0):
    """Run a gadget through the full framework (LocalRuntime + operators)
    while a trigger performs the real system action."""
    import threading
    import inspektor_gadget_tpu.all_gadgets  # noqa: F401
    from inspektor_gadget_tpu.gadgets import GadgetContext, get
    from inspektor_gadget_tpu.runtime import LocalRuntime

    desc = get(category, name)
    params = desc.params().to_params()
    for k, v in flags.items():
        params.set(k, str(v))
    ctx = GadgetContext(desc, gadget_params=params, timeout=timeout)
    events = []
    box = {}

    def _run():
        box["result"] = LocalRuntime().run_gadget(ctx, on_event=events.append)

    th = threading.Thread(target=_run)
    th.start()
    try:
        time.sleep(0.6)
        if trigger:
            trigger()
    finally:
        th.join(timeout + 6)
        ctx.cancel()
        th.join(4)
    result = box.get("result")
    if result is not None:
        assert not result.errors(), result.errors()
    return result, events


@needs_native
@needs_root
def test_trace_open_gadget_real_end_to_end():
    def trigger():
        # repeat the open until the run window closes: under load the
        # capture source may start after the first write, and fanotify
        # only reports opens that happen while the mark is live
        for _ in range(8):
            subprocess.run(["sh", "-c", "date > /tmp/ig_g_open"], check=True)
            time.sleep(0.3)
    _, events = _run_gadget("trace", "open", {"source": "native",
                                              "paths": "/tmp"},
                            trigger, timeout=3.0)
    assert any(e.path == "/tmp/ig_g_open" for e in events)
    hit = next(e for e in events if e.path == "/tmp/ig_g_open")
    assert hit.pid > 0 and hit.comm != ""


@needs_native
@needs_root
def test_trace_bind_gadget_real_end_to_end():
    def trigger():
        # bind again and again until the run window closes: the source
        # reports sockets that appear AFTER its first dump, and under load
        # that dump may come later than any fixed head start
        for _ in range(8):
            s = socket.socket()
            s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
            s.bind(("127.0.0.1", 48714))
            s.listen(1)
            time.sleep(0.3)
            s.close()
    _, events = _run_gadget("trace", "bind", {"source": "native"},
                            trigger, timeout=3.0)
    hits = [e for e in events if e.port == 48714]
    assert hits and hits[0].protocol == "tcp"
    assert hits[0].pid == os.getpid()


@needs_native
@needs_root
def test_trace_capabilities_gadget_real_end_to_end():
    open("/tmp/ig_g_cap", "w").write("x")
    _, events = _run_gadget(
        "trace", "capabilities",
        {"source": "native", "command": "chown 0:0 /tmp/ig_g_cap"},
        timeout=5.0)
    assert any(e.cap == "CHOWN" and e.verdict == "allow" for e in events)


@needs_native
@needs_root
def test_trace_fsslower_gadget_real_end_to_end():
    _, events = _run_gadget(
        "trace", "fsslower",
        {"source": "native", "command": "cat /etc/hostname",
         "min-latency": "0"},
        timeout=5.0)
    assert any(e.file == "/etc/hostname" and e.op == "open" for e in events)


@needs_native
@needs_root
def test_traceloop_real_syscall_history():
    import inspektor_gadget_tpu.all_gadgets  # noqa: F401
    from inspektor_gadget_tpu.gadgets import GadgetContext, get
    desc = get("traceloop", "traceloop")
    params = desc.params().to_params()
    params.set("source", "native")
    params.set("command", "cat /etc/hostname")
    ctx = GadgetContext(desc, gadget_params=params, timeout=6.0)
    g = desc.new_instance(ctx)
    g.run(ctx)
    records = g.read()
    names = {r.syscall for r in records}
    assert "execve" in names and "openat" in names
    opens = [r for r in records if r.syscall == "openat"
             and "/etc/hostname" in r.args]
    assert opens and opens[0].ret == 3
    assert all(r.pid > 0 for r in records)


@needs_native
@needs_root
def test_advise_seccomp_profile_exact_syscall_set():
    import inspektor_gadget_tpu.all_gadgets  # noqa: F401
    from inspektor_gadget_tpu.gadgets import GadgetContext, get
    import json
    desc = get("advise", "seccomp-profile")
    params = desc.params().to_params()
    params.set("source", "native")
    params.set("command", "cat /etc/hostname")
    ctx = GadgetContext(desc, gadget_params=params, timeout=6.0)
    g = desc.new_instance(ctx)
    out = g.run_with_result(ctx)
    profiles = json.loads(out.decode())
    assert profiles, "no profile generated"
    prof = next(iter(profiles.values()))
    names = set(prof["syscalls"][0]["names"])
    # the syscalls cat actually made (beyond the baseline set)
    for expected in ("execve", "openat", "read", "close"):
        assert expected in names
    # and nothing fabricated: a syscall cat never makes must be absent
    assert "reboot" not in names and "swapon" not in names


@needs_native
@needs_root
def test_audit_seccomp_sees_real_denial():
    # A child that drops to uid 1 then chowns a root-owned file: the kernel
    # denies with EPERM — exactly the ERRNO outcome audit/seccomp reports.
    open("/tmp/ig_audit_probe", "w").write("x")
    os.chown("/tmp/ig_audit_probe", 0, 0)
    # -S skips site processing: the child only needs os, and must reach
    # its chown quickly under load
    cmd = ("python -S -c \"import os; os.setuid(1); "
           "os.chown('/tmp/ig_audit_probe', 1, 1)\"")
    _, events = _run_gadget("audit", "seccomp",
                            {"source": "native", "command": cmd},
                            timeout=8.0)
    denied = [e for e in events if e is not None and e.code == "ERRNO"]
    assert any(e.syscall in ("chown", "fchownat") for e in denied), \
        [f"{e.syscall}:{e.code}" for e in events if e is not None]


@needs_native
@needs_root
def test_captrace_source_sees_allows_and_denies():
    """The cap_capable tracepoint window directly: it must observe BOTH
    allowed and denied checks (the property the audit EPERM flavour
    lacks). A root chown exercises CAP_CHOWN allowed; an unprivileged one
    is denied."""
    from inspektor_gadget_tpu.sources.bridge import (
        NativeCapture, SRC_CAP_TRACE, captrace_supported,
    )
    if not captrace_supported():
        pytest.skip("cap_capable tracepoint unavailable")
    target = "/tmp/ig_captrace_probe"
    open(target, "w").close()
    src = NativeCapture(SRC_CAP_TRACE, ring_pow2=18, batch_size=8192)
    src.start()
    try:
        time.sleep(0.5)  # instance + enable
        allows, denies = [], []
        deadline = time.monotonic() + 6.0
        flip = [0]
        while time.monotonic() < deadline and not (allows and denies):
            # root: a REAL ownership change each time (chown to the current
            # owner short-circuits before the capability check)
            flip[0] ^= 1
            os.chown(target, 65534 * flip[0], 65534 * flip[0])
            subprocess.run(
                ["setpriv", "--reuid", "65534", "--clear-groups",
                 "chown", "0:0", target],
                check=False, stderr=subprocess.DEVNULL)  # denied
            time.sleep(0.3)
            b = src.pop()
            c = b.cols
            for i in range(b.count):
                if int(c["kind"][i]) != 12 or int(c["aux2"][i]) != 0:
                    continue  # EV_CAPABILITY, CAP_CHOWN only
                (allows if int(c["aux1"][i]) else denies).append(
                    (int(c["pid"][i]), b.comm_str(i)))
        assert allows, "no allowed CAP_CHOWN check observed"
        assert denies, "no denied CAP_CHOWN check observed"
        assert all(pid > 0 and comm for pid, comm in allows + denies)
    finally:
        src.stop()
        src.close()
        os.unlink(target)


@needs_native
@needs_root
def test_audit_source_eperm_rules_capability_denial():
    """The NETLINK_AUDIT flavour directly (the gadget prefers the
    cap_capable tracepoint when available, so this window needs its own
    coverage): EPERM exit rules surface an unprivileged chown as a
    capability denial, and rules + audit state are restored at close."""
    from inspektor_gadget_tpu.sources.bridge import (
        NativeCapture, SRC_AUDIT, audit_supported, make_cfg,
    )
    if not audit_supported():
        pytest.skip("audit window unavailable")
    target = "/tmp/ig_auditsrc_probe"
    open(target, "w").close()
    src = NativeCapture(SRC_AUDIT, ring_pow2=16, batch_size=4096,
                        cfg=make_cfg(eperm_rules=1))
    src.start()
    try:
        time.sleep(0.8)  # rule install
        deadline = time.monotonic() + 6.0
        denials = []
        while time.monotonic() < deadline and not denials:
            subprocess.run(
                ["setpriv", "--reuid", "65534", "--clear-groups",
                 "chown", "0:0", target],
                check=False, stderr=subprocess.DEVNULL)
            time.sleep(0.3)
            b = src.pop()
            c = b.cols
            for i in range(b.count):
                if (int(c["kind"][i]) == 12       # EV_CAPABILITY
                        and int(c["aux1"][i]) == 0  # deny
                        and int(c["aux2"][i]) == 0):  # CAP_CHOWN
                    denials.append((int(c["pid"][i]), int(c["uid"][i])))
        assert denials, "no CAP_CHOWN denial from the audit window"
        assert all(uid == 65534 for _pid, uid in denials)
    finally:
        src.stop()
        src.close()
        os.unlink(target)


@needs_native
@needs_root
def test_profile_cpu_perf_sampler_real_samples():
    import inspektor_gadget_tpu.all_gadgets  # noqa: F401
    from inspektor_gadget_tpu.gadgets import GadgetContext, get
    import threading
    spin = subprocess.Popen(
        ["python", "-S", "-c",
         "import time,sys\nt=time.time()\nwhile time.time()-t<6: pass"])
    try:
        desc = get("profile", "cpu")
        params = desc.params().to_params()
        params.set("sampler", "perf")
        params.set("profile-output", "folded")
        params.set("pid", str(spin.pid))
        ctx = GadgetContext(desc, gadget_params=params, timeout=2.5)
        g = desc.new_instance(ctx)
        timer = threading.Timer(2.5, ctx.cancel)
        timer.start()
        out = g.run_with_result(ctx).decode()
        timer.cancel()
    finally:
        spin.kill()
        spin.wait()
    lines = [l for l in out.splitlines() if l.strip()]
    assert lines, "no perf samples for a spinning child"
    total = sum(int(l.rsplit(" ", 1)[1]) for l in lines)
    # 49 Hz over ~2.5s on the spinning pid → expect a healthy fraction
    assert total >= 20, f"only {total} samples"
    assert any(l.startswith("python;") for l in lines)


@needs_native
@needs_root
def test_capture_side_filter_counts_and_blocks():
    """The C++ mntns filter drops events before the ring and accounts them
    (tracer-collection mntnsset contract)."""
    src = NativeCapture(SRC_SYNTH_EXEC, seed=5, rate=200_000, vocab=100,
                        ring_pow2=16)
    # synthetic events use mntns 4026531840+idx%64; allow exactly one
    allowed = {4026531840 + 7}
    src.set_filter(allowed)
    src.start()
    time.sleep(0.4)
    src.stop()
    popped = 0
    bad = 0
    while True:
        b = src.pop()
        if b.count == 0:
            break
        popped += b.count
        bad += int((~np.isin(b.cols["mntns"][:b.count],
                             np.fromiter(allowed, np.uint64))).sum())
    filtered = src.filtered()
    src.close()
    assert bad == 0, "filtered event leaked into the ring"
    assert popped > 0, "allowed mntns never captured"
    assert filtered > popped, "filtered accounting missing"
