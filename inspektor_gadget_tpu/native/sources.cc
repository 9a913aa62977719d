// Event capture sources.
//
// The reference's L0 is eBPF programs attached to tracepoints/kprobes
// (SURVEY §2.4); in this build the native capture layer is C++:
//  - SyntheticSource: deterministic zipf-distributed event generator — the
//    replayable test/bench backbone (the analogue of the reference's
//    namespace-unshare fake containers + event triggers,
//    internal/test/runner.go).
//  - ProcExecSource: real exec/exit capture via netlink proc connector
//    (PROC_EVENT_EXEC/EXIT) with /proc polling fallback — the non-eBPF
//    kernel boundary for trace/exec + trace/signal-ish lifecycles.
//  - ProcTcpSource: /proc/net/tcp{,6} diff scanner for connect/accept/close
//    (trace/tcp family without a socket filter).
//
// Every source owns an SPSC ring; a drop is counted, never blocks capture.

#include <fcntl.h>
#include <pthread.h>
#include <sys/stat.h>
#include <unistd.h>

#include <cerrno>
#include <cmath>
#include <cstdio>
#include <cstdlib>
#include <cstring>
#include <ctime>
#include <map>
#include <memory>
#include <mutex>
#include <set>
#include <string>
#include <thread>
#include <unordered_map>
#include <unordered_set>
#include <vector>

#ifdef __linux__
#include <dirent.h>
#include <linux/cn_proc.h>
#include <linux/connector.h>
#include <linux/netlink.h>
#include <sys/socket.h>
#endif

#include "ringbuf.h"

namespace ig {

static uint64_t now_ns() {
  struct timespec ts;
  clock_gettime(CLOCK_REALTIME, &ts);
  return (uint64_t)ts.tv_sec * 1000000000ull + ts.tv_nsec;
}

// ---------------------------------------------------------------------------
// Vocab: hash -> string side table for un-hashing heavy hitters.
// ---------------------------------------------------------------------------

class Vocab {
 public:
  void put(uint64_t h, const char* s, size_t n) {
    std::lock_guard<std::mutex> g(mu_);
    if (map_.size() >= cap_) return;  // consumers fall back to hex keys
    auto it = map_.find(h);
    if (it == map_.end()) map_.emplace(h, std::string(s, n));
  }

  // Bound the side table for high-cardinality producers (per-call-unique
  // syscall lines would otherwise grow it for the life of the source).
  void set_capacity(size_t cap) {
    std::lock_guard<std::mutex> g(mu_);
    cap_ = cap;
  }
  // returns copied length, 0 if unknown
  size_t get(uint64_t h, char* out, size_t cap) {
    std::lock_guard<std::mutex> g(mu_);
    auto it = map_.find(h);
    if (it == map_.end()) return 0;
    size_t n = it->second.size() < cap ? it->second.size() : cap;
    memcpy(out, it->second.data(), n);
    return n;
  }

 private:
  std::mutex mu_;
  std::unordered_map<uint64_t, std::string> map_;
  size_t cap_ = (size_t)-1;
};

// ---------------------------------------------------------------------------
// Source base
// ---------------------------------------------------------------------------

class Source {
 public:
  explicit Source(size_t ring_pow2) : ring_(ring_pow2) {}
  // Derived classes MUST stop() in their own destructor: the capture thread
  // runs derived run() and reads derived members, which are destroyed before
  // this base destructor joins the thread.
  virtual ~Source() { stop(); }

  virtual void start() {
    // cpu_mu_ guards every access to thread_ (assignment here, the final
    // sample in stop(), joinable()/native_handle() reads in
    // thread_cpu_ns()) — std::thread itself is not atomic
    std::lock_guard<std::mutex> g(cpu_mu_);
    running_.store(true);
    thread_ = std::thread([this] { run(); });
  }
  virtual void stop() {
    // Sample the CPU clock and move the handle out under cpu_mu_, then
    // join OUTSIDE the lock: a capture thread blocked in a long syscall
    // must not stall stats readers (ig_sources_stats holds g_mu while
    // waiting on cpu_mu_, so a held-across-join cpu_mu_ would freeze the
    // whole C API behind one slow shutdown).
    std::thread t;
    {
      std::lock_guard<std::mutex> g(cpu_mu_);
      bool was = running_.exchange(false);
      if (was && thread_.joinable()) {
        sample_cpu_locked();
        t = std::move(thread_);
      }
    }
    if (t.joinable()) t.join();
  }

  size_t pop(Event* out, size_t n) { return ring_.pop(out, n); }
  uint64_t drops() const { return ring_.drops(); }
  uint64_t produced() const { return ring_.produced(); }
  uint64_t filtered() const {
    return filtered_.load(std::memory_order_relaxed);
  }
  Vocab& vocab() { return vocab_; }

  // -- self-stats (the top/ebpf contract: per-program runtime via kernel
  //    stats, pkg/gadgets/top/ebpf/tracer.go:55-418 + pkg/bpfstats) -------
  void set_kind(uint32_t k) { kind_ = k; }
  uint32_t kind() const { return kind_; }
  uint64_t ring_len() const { return ring_.size(); }
  uint64_t ring_capacity() const { return ring_.capacity(); }
  uint64_t consumed() const { return ring_.consumed(); }
  // CPU time consumed by this source's capture thread (ns); the analogue
  // of BPF_ENABLE_STATS run_time_ns per program.
  uint64_t thread_cpu_ns() {
    std::lock_guard<std::mutex> g(cpu_mu_);
    if (running_.load(std::memory_order_relaxed) && thread_.joinable())
      sample_cpu_locked();
    return last_cpu_ns_;
  }

  // Capture-side container filter — the mntnsset-map analogue
  // (ref: pkg/tracer-collection/tracer-collection.go:100-134 keeps a per-
  // tracer BPF hash of allowed mntns ids so events are discarded *before*
  // they ever reach userspace). Here the set is swapped in atomically from
  // the tracer-collection pubsub; capture threads consult it pre-push, so a
  // filtered gadget does zero per-event Python work and every suppressed
  // event is accounted.
  void set_filter(const uint64_t* ids, size_t n) {
    std::shared_ptr<const std::unordered_set<uint64_t>> f;
    if (ids != nullptr)
      f = std::make_shared<const std::unordered_set<uint64_t>>(ids, ids + n);
    std::lock_guard<std::mutex> g(filter_mu_);
    filter_ = std::move(f);
  }

 protected:
  virtual void run() = 0;

  // Push through the filter; every event a capture thread emits goes here.
  bool emit(const Event& ev) {
    {
      std::shared_ptr<const std::unordered_set<uint64_t>> f;
      {
        std::lock_guard<std::mutex> g(filter_mu_);
        f = filter_;
      }
      if (f && !f->count(ev.mntns)) {
        filtered_.fetch_add(1, std::memory_order_relaxed);
        return false;
      }
    }
    return ring_.push(ev);
  }

  RingBuffer ring_;
  Vocab vocab_;
  std::atomic<bool> running_{false};
  std::thread thread_;
  std::mutex filter_mu_;
  std::shared_ptr<const std::unordered_set<uint64_t>> filter_;
  std::atomic<uint64_t> filtered_{0};

 private:
  void sample_cpu_locked() {
#ifdef __linux__
    clockid_t cid;
    if (pthread_getcpuclockid(thread_.native_handle(), &cid) == 0) {
      struct timespec ts;
      if (clock_gettime(cid, &ts) == 0)
        last_cpu_ns_ =
            (uint64_t)ts.tv_sec * 1000000000ull + (uint64_t)ts.tv_nsec;
    }
#endif
  }

  uint32_t kind_ = 0;
  std::mutex cpu_mu_;
  uint64_t last_cpu_ns_ = 0;
};

#ifdef __linux__
// Shared /proc identity fill: comm (hashed into the vocab) + mntns.
// Used by every procfs-adjacent source; the self-enrichment role of the
// reference's containers-map lookup inside BPF programs.
inline void fill_proc_identity(Event& ev, Vocab& vocab, uint32_t pid) {
  char path[64], buf[256];
  snprintf(path, sizeof(path), "/proc/%u/comm", pid);
  int fd = open(path, O_RDONLY);
  ssize_t n = 0;
  if (fd >= 0) {
    n = read(fd, buf, sizeof(buf) - 1);
    close(fd);
  }
  if (n > 0 && buf[n - 1] == '\n') n--;
  if (n > 0) {
    ev.key_hash = fnv1a64(buf, (size_t)n);
    vocab.put(ev.key_hash, buf, (size_t)n);
    size_t c = (size_t)n < sizeof(ev.comm) - 1 ? (size_t)n : sizeof(ev.comm) - 1;
    memcpy(ev.comm, buf, c);
  }
  snprintf(path, sizeof(path), "/proc/%u/ns/mnt", pid);
  char link[64];
  ssize_t ln = readlink(path, link, sizeof(link) - 1);
  if (ln > 0) {
    link[ln] = 0;
    const char* lb = strchr(link, '[');
    if (lb) ev.mntns = strtoull(lb + 1, nullptr, 10);
  }
}
#endif  // __linux__

// ---------------------------------------------------------------------------
// SyntheticSource — seeded zipf generator over a comm/addr vocabulary.
// ---------------------------------------------------------------------------

class SyntheticSource : public Source {
 public:
  SyntheticSource(size_t ring_pow2, uint32_t kind, uint64_t seed,
                  double rate_per_sec, uint32_t vocab_size, double zipf_s,
                  uint32_t containers)
      : Source(ring_pow2),
        kind_(kind),
        rng_(seed ? seed : 0x9E3779B97F4A7C15ull),
        rate_(rate_per_sec),
        vocab_size_(vocab_size ? vocab_size : 1000),
        zipf_s_(zipf_s > 0 ? zipf_s : 1.2),
        containers_(containers ? containers : 64) {
    // Zipf sampling via Walker's alias method: O(1) per draw (one random,
    // one table probe) instead of a CDF binary search — keeps the host
    // generation path well above the device-feed requirement.
    std::vector<double> p(vocab_size_);
    double sum = 0;
    for (uint32_t i = 0; i < vocab_size_; i++) {
      p[i] = 1.0 / std::pow((double)(i + 1), zipf_s_);
      sum += p[i];
    }
    alias_prob_.resize(vocab_size_);
    alias_idx_.resize(vocab_size_);
    std::vector<uint32_t> small, large;
    std::vector<double> scaled(vocab_size_);
    for (uint32_t i = 0; i < vocab_size_; i++) {
      scaled[i] = p[i] / sum * vocab_size_;
      (scaled[i] < 1.0 ? small : large).push_back(i);
    }
    while (!small.empty() && !large.empty()) {
      uint32_t s = small.back(); small.pop_back();
      uint32_t l = large.back(); large.pop_back();
      alias_prob_[s] = scaled[s];
      alias_idx_[s] = l;
      scaled[l] = scaled[l] + scaled[s] - 1.0;
      (scaled[l] < 1.0 ? small : large).push_back(l);
    }
    for (uint32_t i : small) { alias_prob_[i] = 1.0; alias_idx_[i] = i; }
    for (uint32_t i : large) { alias_prob_[i] = 1.0; alias_idx_[i] = i; }
    names_.reserve(vocab_size_);
    for (uint32_t i = 0; i < vocab_size_; i++) {
      char buf[24];
      int n = snprintf(buf, sizeof(buf), "proc-%u", i);
      names_.emplace_back(buf, n);
      uint64_t h = fnv1a64(buf, n);
      hashes_.push_back(h);
      vocab_.put(h, buf, n);
    }
  }

  ~SyntheticSource() override { stop(); }

  // Fill a caller buffer directly — the zero-copy bench path (no thread).
  // One clock read per batch: the bridge stamps batch-level timestamps.
  size_t generate(Event* out, size_t n) {
    uint64_t ts = now_ns();
    for (size_t i = 0; i < n; i++) out[i] = make_event(ts);
    return n;
  }

  // Folded-uint32 fast path: the sketch plane consumes xor-folded uint32
  // keys, so fold once per vocab entry and emit draws straight into the
  // caller's H2D staging buffer — no 64-byte Event structs, no separate
  // numpy fold pass. One alias draw + one table load per event.
  size_t generate_folded(uint32_t* out, size_t n) {
    if (folded_.empty()) {
      folded_.reserve(hashes_.size());
      for (uint64_t h : hashes_)
        folded_.push_back((uint32_t)((h >> 32) ^ (h & 0xFFFFFFFFull)));
    }
    for (size_t i = 0; i < n; i++) out[i] = folded_[zipf_draw()];
    return n;
  }

 protected:
  void run() override {
    // Paced producer: emit in 1ms chunks at the requested rate.
    const double per_ms = rate_ / 1000.0;
    double carry = 0;
    while (running_.load(std::memory_order_relaxed)) {
      carry += per_ms;
      size_t n = (size_t)carry;
      carry -= (double)n;
      for (size_t i = 0; i < n; i++) emit(make_event());
      std::this_thread::sleep_for(std::chrono::milliseconds(1));
    }
  }

 private:
  uint64_t next_rand() {  // splitmix64
    uint64_t z = (rng_ += 0x9E3779B97F4A7C15ull);
    z = (z ^ (z >> 30)) * 0xBF58476D1CE4E5B9ull;
    z = (z ^ (z >> 27)) * 0x94D049BB133111EBull;
    return z ^ (z >> 31);
  }

  uint32_t zipf_draw() {
    uint64_t r = next_rand();
    uint32_t i = (uint32_t)((r >> 32) % vocab_size_);
    double u = (double)(r & 0xFFFFFFFF) * (1.0 / 4294967296.0);
    return u < alias_prob_[i] ? i : alias_idx_[i];
  }

  Event make_event(uint64_t ts = 0) {
    Event ev{};
    uint32_t idx = zipf_draw();
    ev.ts_ns = ts ? ts : now_ns();
    ev.key_hash = hashes_[idx];
    ev.pid = 1000 + (uint32_t)(next_rand() % 50000);
    ev.ppid = 1;
    ev.uid = (uint32_t)(next_rand() % 4);
    ev.kind = kind_;
    ev.mntns = 4026531840ull + idx % containers_;  // fake containers
    ev.aux1 = next_rand();                // e.g. addresses / bytes
    ev.aux2 = next_rand() & 0xFFFF;       // e.g. port / flags
    const std::string& nm = names_[idx];
    size_t n = nm.size() < sizeof(ev.comm) ? nm.size() : sizeof(ev.comm) - 1;
    memcpy(ev.comm, nm.data(), n);
    return ev;
  }

  uint32_t kind_;
  uint64_t rng_;
  double rate_;
  uint32_t vocab_size_;
  double zipf_s_;
  uint32_t containers_;
  std::vector<double> alias_prob_;
  std::vector<uint32_t> alias_idx_;
  std::vector<std::string> names_;
  std::vector<uint64_t> hashes_;
  std::vector<uint32_t> folded_;
};

#ifdef __linux__

// ---------------------------------------------------------------------------
// ProcExecSource — netlink proc connector exec/exit events, /proc fallback.
// ---------------------------------------------------------------------------

class ProcExecSource : public Source {
 public:
  explicit ProcExecSource(size_t ring_pow2) : Source(ring_pow2) {}
  ~ProcExecSource() override { stop(); }

 protected:
  void run() override {
    if (!run_netlink()) run_procfs();
  }

 private:
  void fill_from_proc(Event& ev, uint32_t pid) {
    fill_proc_identity(ev, vocab_, pid);
    if (ev.key_hash == 0) {
      char buf[32];
      int n = snprintf(buf, sizeof(buf), "pid-%u", pid);
      ev.key_hash = fnv1a64(buf, (size_t)n);
      vocab_.put(ev.key_hash, buf, (size_t)n);
      memcpy(ev.comm, buf, (size_t)n < sizeof(ev.comm) - 1 ? (size_t)n
                                                           : sizeof(ev.comm) - 1);
    }
    // ppid + real uid: execsnoop's columns (the BPF event carries them
    // from task_struct; here one /proc/<pid>/status read — NOT the
    // /proc/<pid> inode owner, which the kernel forces to root for
    // non-dumpable processes, i.e. every setuid exec). Best effort — an
    // exec-and-exit racer may already be gone.
    char path[64];
    snprintf(path, sizeof(path), "/proc/%u/status", pid);
    int fd = open(path, O_RDONLY);
    if (fd >= 0) {
      char sb[1024];
      ssize_t n = read(fd, sb, sizeof(sb) - 1);
      close(fd);
      if (n > 0) {
        sb[n] = 0;
        const char* pp = strstr(sb, "\nPPid:");
        unsigned v = 0;
        if (pp && sscanf(pp + 6, " %u", &v) == 1) ev.ppid = v;
        const char* up = strstr(sb, "\nUid:");
        if (up && sscanf(up + 5, " %u", &v) == 1) ev.uid = v;  // real uid
      }
    }
    // argv: /proc/<pid>/cmdline, NUL-separated → spaces, vocab under aux1
    // (execsnoop's ARGS column; tracer.go:169-181 parses the same buffer,
    // itself capped in-kernel). A line beyond the buffer is marked "..."
    // so truncation is visible and distinct commands can't silently
    // collapse onto a shared prefix hash.
    snprintf(path, sizeof(path), "/proc/%u/cmdline", pid);
    fd = open(path, O_RDONLY);
    if (fd >= 0) {
      char ab[2048];
      // read 3 bytes short of the buffer so the marker ALWAYS fits — a
      // cap landing mid-argument is the common truncation case
      ssize_t n = read(fd, ab, sizeof(ab) - 4);
      close(fd);
      bool truncated = n == (ssize_t)sizeof(ab) - 4;
      while (n > 0 && ab[n - 1] == 0) n--;  // trailing NUL(s)
      if (n > 0) {
        for (ssize_t i = 0; i < n; i++)
          if (ab[i] == 0) ab[i] = ' ';
        if (truncated) {
          memcpy(ab + n, "...", 3);
          n += 3;
        }
        ev.aux1 = fnv1a64(ab, (size_t)n);
        vocab_.put(ev.aux1, ab, (size_t)n);
      }
    }
  }

  bool run_netlink() {
    int sock = socket(PF_NETLINK, SOCK_DGRAM | SOCK_NONBLOCK, NETLINK_CONNECTOR);
    if (sock < 0) return false;
    struct sockaddr_nl addr {};
    addr.nl_family = AF_NETLINK;
    addr.nl_groups = CN_IDX_PROC;
    addr.nl_pid = (uint32_t)getpid();
    if (bind(sock, (struct sockaddr*)&addr, sizeof(addr)) < 0) {
      close(sock);
      return false;
    }
    // subscribe: PROC_CN_MCAST_LISTEN. cn_msg ends in a flexible array
    // member, so the request is assembled in a flat buffer.
    char req[NLMSG_LENGTH(sizeof(struct cn_msg) + sizeof(enum proc_cn_mcast_op))];
    memset(req, 0, sizeof(req));
    struct nlmsghdr* hdr = (struct nlmsghdr*)req;
    hdr->nlmsg_len = sizeof(req);
    hdr->nlmsg_type = NLMSG_DONE;
    hdr->nlmsg_pid = (uint32_t)getpid();
    struct cn_msg* msg = (struct cn_msg*)NLMSG_DATA(hdr);
    msg->id.idx = CN_IDX_PROC;
    msg->id.val = CN_VAL_PROC;
    msg->len = sizeof(enum proc_cn_mcast_op);
    *(enum proc_cn_mcast_op*)msg->data = PROC_CN_MCAST_LISTEN;
    if (send(sock, req, sizeof(req), 0) < 0) {
      close(sock);
      return false;
    }
    char buf[4096];
    bool got_any = false;
    uint64_t start = now_ns();
    while (running_.load(std::memory_order_relaxed)) {
      ssize_t len = recv(sock, buf, sizeof(buf), 0);
      if (len <= 0) {
        if (errno == EAGAIN || errno == EWOULDBLOCK) {
          // If netlink stays silent for 2s with no permission, fall back.
          if (!got_any && now_ns() - start > 2000000000ull) {
            close(sock);
            return false;
          }
          std::this_thread::sleep_for(std::chrono::milliseconds(5));
          continue;
        }
        break;
      }
      for (struct nlmsghdr* h = (struct nlmsghdr*)buf; NLMSG_OK(h, (size_t)len);
           h = NLMSG_NEXT(h, len)) {
        struct cn_msg* cn = (struct cn_msg*)NLMSG_DATA(h);
        struct proc_event* pe = (struct proc_event*)cn->data;
        Event ev{};
        ev.ts_ns = now_ns();
        got_any = true;
        if (pe->what == proc_event::PROC_EVENT_EXEC) {
          ev.kind = EV_EXEC;
          ev.pid = (uint32_t)pe->event_data.exec.process_pid;
          fill_from_proc(ev, ev.pid);
          emit(ev);
        } else if (pe->what == proc_event::PROC_EVENT_EXIT) {
          ev.kind = EV_EXIT;
          ev.pid = (uint32_t)pe->event_data.exit.process_pid;
          ev.aux2 = (uint64_t)pe->event_data.exit.exit_code;
          emit(ev);
          // Termination by signal is kernel-real signal-delivery evidence:
          // exit_code follows wait(2) encoding, low 7 bits = fatal signal
          // (sigsnoop's system-wide window without eBPF; the ptrace source
          // covers full delivery for traced trees).
          uint32_t sig = (uint32_t)pe->event_data.exit.exit_code & 0x7f;
          if (sig != 0) {
            Event sv = ev;
            sv.kind = EV_SIGNAL;
            sv.ppid = ev.pid;  // receiver (tpid); sender unknown post-mortem
            sv.aux2 = sig;
            sv.aux1 = 1;  // delivered+fatal
            emit(sv);
          }
        }
      }
    }
    close(sock);
    return true;
  }

  void run_procfs() {
    // Poll /proc for new pids at 50Hz — the BCC-less fallback flavour
    // (role analogue of pkg/standardgadgets' subprocess fallback).
    std::set<uint32_t> seen;
    bool first = true;
    while (running_.load(std::memory_order_relaxed)) {
      DIR* d = opendir("/proc");
      if (!d) return;
      std::set<uint32_t> cur;
      struct dirent* de;
      while ((de = readdir(d))) {
        char* end;
        unsigned long pid = strtoul(de->d_name, &end, 10);
        if (*end || pid == 0) continue;
        cur.insert((uint32_t)pid);
      }
      closedir(d);
      if (!first) {
        for (uint32_t pid : cur) {
          if (!seen.count(pid)) {
            Event ev{};
            ev.ts_ns = now_ns();
            ev.kind = EV_EXEC;
            ev.pid = pid;
            fill_from_proc(ev, pid);
            emit(ev);
          }
        }
        for (uint32_t pid : seen) {
          if (!cur.count(pid)) {
            Event ev{};
            ev.ts_ns = now_ns();
            ev.kind = EV_EXIT;
            ev.pid = pid;
            emit(ev);
          }
        }
      }
      seen.swap(cur);
      first = false;
      std::this_thread::sleep_for(std::chrono::milliseconds(20));
    }
  }
};

// ---------------------------------------------------------------------------
// ProcTcpSource — /proc/net/tcp{,6} diff scanner.
// ---------------------------------------------------------------------------

class ProcTcpSource : public Source {
 public:
  explicit ProcTcpSource(size_t ring_pow2) : Source(ring_pow2) {}
  ~ProcTcpSource() override { stop(); }

 protected:
  void run() override {
    std::map<uint64_t, Event> known;  // inode -> last event
    bool first = true;
    uint64_t last_opens = 0;
    while (running_.load(std::memory_order_relaxed)) {
      std::map<uint64_t, Event> cur;
      scan("/proc/net/tcp", cur);
      scan("/proc/net/tcp6", cur);
      size_t new_seen = 0;
      if (!first) {
        for (auto& [inode, ev] : cur) {
          auto it = known.find(inode);
          if (it == known.end()) {
            Event e = ev;
            // state 0x0A = LISTEN → accept-side socket; else connect
            e.kind = (e.aux2 >> 32) == 0x0A ? EV_TCP_ACCEPT : EV_TCP_CONNECT;
            emit(e);
            new_seen++;
          }
        }
        for (auto& [inode, ev] : known) {
          if (!cur.count(inode)) {
            Event e = ev;
            e.kind = EV_TCP_CLOSE;
            e.ts_ns = now_ns();
            emit(e);
          }
        }
      }
      // Churn accounting: connections opened and closed entirely between
      // two 50ms scans are invisible to the diff (the reference's kprobe
      // path sees every connect — tcpconnect.bpf.c). The kernel's SNMP
      // ActiveOpens+PassiveOpens counters give ground truth; any excess
      // over sockets we actually observed is surfaced as a drop so the
      // loss stays auditable end-to-end.
      uint64_t opens = snmp_tcp_opens();
      if (last_opens != 0 && opens > last_opens) {
        uint64_t delta = opens - last_opens;
        if (delta > new_seen) ring_.count_external_drops(delta - new_seen);
      }
      if (opens != 0) last_opens = opens;
      known.swap(cur);
      first = false;
      std::this_thread::sleep_for(std::chrono::milliseconds(50));
    }
  }

 private:
  // Sum of TCP ActiveOpens + PassiveOpens from /proc/net/snmp.
  static uint64_t snmp_tcp_opens() {
    FILE* f = fopen("/proc/net/snmp", "r");
    if (!f) return 0;
    char line[1024];
    uint64_t active = 0, passive = 0;
    bool header_seen = false;
    while (fgets(line, sizeof(line), f)) {
      if (strncmp(line, "Tcp:", 4) != 0) continue;
      if (!header_seen) {
        header_seen = true;  // first Tcp: line is the field-name header
        continue;
      }
      // Tcp: RtoAlgorithm RtoMin RtoMax MaxConn ActiveOpens PassiveOpens ...
      sscanf(line, "Tcp: %*s %*s %*s %*s %llu %llu",
             (unsigned long long*)&active, (unsigned long long*)&passive);
      break;
    }
    fclose(f);
    return active + passive;
  }
  void scan(const char* path, std::map<uint64_t, Event>& out) {
    FILE* f = fopen(path, "r");
    if (!f) return;
    char line[512];
    if (!fgets(line, sizeof(line), f)) {  // header
      fclose(f);
      return;
    }
    while (fgets(line, sizeof(line), f)) {
      unsigned long sl;
      char local[128], remote[128];
      unsigned state;
      unsigned long long inode = 0;
      // sl local rem st tx:rx tr:tm retrnsmt uid timeout inode
      int n = sscanf(line, " %lu: %127s %127s %x %*s %*s %*s %*u %*u %llu", &sl,
                     local, remote, &state, &inode);
      if (n < 5 || inode == 0) continue;
      Event ev{};
      ev.ts_ns = now_ns();
      unsigned long long laddr = 0, raddr = 0;
      unsigned lport = 0, rport = 0;
      char* colon = strrchr(local, ':');
      if (colon) {
        lport = (unsigned)strtoul(colon + 1, nullptr, 16);
        laddr = strtoull(local, nullptr, 16);
      }
      colon = strrchr(remote, ':');
      if (colon) {
        rport = (unsigned)strtoul(colon + 1, nullptr, 16);
        raddr = strtoull(remote, nullptr, 16);
      }
      ev.aux1 = (laddr << 32) ^ raddr;
      ev.aux2 = ((uint64_t)state << 32) | (lport << 16) | rport;
      char key[64];
      int kn = snprintf(key, sizeof(key), "%llx:%x->%llx:%x", laddr, lport,
                        raddr, rport);
      ev.key_hash = fnv1a64(key, (size_t)kn);
      vocab_.put(ev.key_hash, key, (size_t)kn);
      ev.kind = EV_TCP_CONNECT;
      out[inode] = ev;
    }
    fclose(f);
  }
};

#endif  // __linux__

}  // namespace ig
