// Batched UDP I/O engine for the host data plane.
//
// The reference's packet I/O is java.net sockets with one thread per
// connector stream (org.jitsi.impl.neomedia.RTPConnectorUDPImpl et al.);
// at 10k streams that design melts.  This engine is the TPU-native
// replacement (SURVEY §2.6 item 12): recvmmsg/sendmmsg syscall batching,
// SO_REUSEPORT fan-in, and a receive buffer whose memory layout IS the
// framework's PacketBatch struct-of-arrays ([max_pkts, capacity] uint8
// matrix + int32 length vector) so datagrams land ready for the device
// with zero repacking.
//
// Exposed as a plain C ABI for ctypes (no pybind11 in this image).

#include <arpa/inet.h>
#include <atomic>
#include <cerrno>
#include <condition_variable>
#include <csignal>
#include <cstdint>
#include <cstring>
#include <ctime>
#include <deque>
#include <mutex>
#include <netinet/in.h>
#include <new>
#include <poll.h>
#include <sys/socket.h>
#include <sys/types.h>
#include <system_error>
#include <thread>
#include <unistd.h>
#include <unordered_map>
#include <vector>

// ===========================================================================
// Egress worker: the fan-out's sendmmsg off the caller's thread.
//
// One worker thread a socket, started by the socket's first
// udp_send_async and joined in udp_close.  A caller hands over a burst
// (the same arguments udp_send_batch takes) and goes on; the worker
// builds the mmsghdrs, runs the same sendmmsg loop and publishes a
// completion: job id, `sent` or -errno, and CLOCK_MONOTONIC stamps of
// the send's start and end (Python's time.perf_counter clock).
//
// ORDER is the contract.  One worker, FIFO; and a synchronous send on a
// socket that has a worker first waits until the worker has nothing
// queued or in flight, so a leg never sees a later datagram before an
// earlier one.  The queue is bounded: a full queue makes the hand-over
// wait, so overload shows as the caller's time and not as memory.
//
// The caller owns every array of a job (plane, lengths, addresses) and
// keeps it alive and unwritten until the job's completion is reaped;
// nothing is copied.  A socket has one sending caller at a time, as it
// always had (the fd itself is not guarded against a close during a
// send either).  No Python object is touched on the worker.

namespace {

//: jobs that may wait behind the one in flight before the hand-over
//: blocks.  A constant, not a setting: a tick hands over one burst, so
//: more than a few queued means the worker is the pace
constexpr int kEgressQueueJobs = 4;

int64_t monotonic_ns() {
  timespec ts{};
  clock_gettime(CLOCK_MONOTONIC, &ts);
  return static_cast<int64_t>(ts.tv_sec) * 1000000000LL + ts.tv_nsec;
}

// the sendmmsg loop both paths run: scratch is the calling thread's own
int send_burst(int fd, const uint8_t *buf, int capacity,
               const int32_t *lengths, const uint32_t *dst_ip,
               const uint16_t *dst_port, const int32_t *idx, int n) {
  thread_local std::vector<mmsghdr> hdrs;
  thread_local std::vector<iovec> iovs;
  thread_local std::vector<sockaddr_in> addrs;
  if (static_cast<int>(hdrs.size()) < n) {
    hdrs.resize(n);
    iovs.resize(n);
    addrs.resize(n);
  }
  for (int i = 0; i < n; i++) {
    int row = idx ? idx[i] : i;
    iovs[i].iov_base = const_cast<uint8_t *>(buf) +
                       static_cast<size_t>(row) * capacity;
    iovs[i].iov_len = lengths[i];
    addrs[i] = sockaddr_in{};
    addrs[i].sin_family = AF_INET;
    addrs[i].sin_port = htons(dst_port[i]);
    addrs[i].sin_addr.s_addr = htonl(dst_ip[i]);
    std::memset(&hdrs[i], 0, sizeof(mmsghdr));
    hdrs[i].msg_hdr.msg_iov = &iovs[i];
    hdrs[i].msg_hdr.msg_iovlen = 1;
    hdrs[i].msg_hdr.msg_name = &addrs[i];
    hdrs[i].msg_hdr.msg_namelen = sizeof(sockaddr_in);
  }
  int sent = 0;
  while (sent < n) {
    int r = sendmmsg(fd, hdrs.data() + sent, n - sent, 0);
    if (r < 0) {
      if (errno == EAGAIN || errno == EWOULDBLOCK) break;
      return -errno;
    }
    sent += r;
  }
  return sent;
}

struct EgressJob {
  int64_t id;
  const uint8_t *buf;
  int capacity;
  const int32_t *lengths;
  const uint32_t *dst_ip;
  const uint16_t *dst_port;
  int n;
};

struct EgressDone {
  int64_t id;
  int sent;  // datagrams sent, or -errno
  int64_t t0_ns, t1_ns;
};

struct EgressWorker {
  int fd = -1;
  std::mutex mu;
  std::condition_variable work;  // the worker waits here for a job
  std::condition_variable room;  // callers wait here for room / idle
  std::deque<EgressJob> queue;   // handed over, not yet taken
  std::deque<EgressDone> done;   // completed, not yet reaped
  bool busy = false;             // a job is in the worker's hands
  bool stop = false;
  int64_t next_id = 1;
  std::thread thread;

  void run() {
    // the process's signals are the interpreter's: none is taken here,
    // so no send of the worker ends in EINTR
    sigset_t all;
    sigfillset(&all);
    pthread_sigmask(SIG_BLOCK, &all, nullptr);
    std::unique_lock<std::mutex> lk(mu);
    for (;;) {
      work.wait(lk, [this] { return stop || !queue.empty(); });
      if (queue.empty()) return;  // stop, and nothing left to send
      EgressJob job = queue.front();
      queue.pop_front();
      busy = true;
      room.notify_all();
      lk.unlock();
      int64_t t0 = monotonic_ns();
      int sent = send_burst(fd, job.buf, job.capacity, job.lengths,
                            job.dst_ip, job.dst_port, nullptr, job.n);
      int64_t t1 = monotonic_ns();
      lk.lock();
      done.push_back(EgressDone{job.id, sent, t0, t1});
      busy = false;
      room.notify_all();
    }
  }

  bool idle() const { return queue.empty() && !busy; }
};

std::mutex g_egress_mu;
std::unordered_map<int, EgressWorker *> g_egress;  // fd -> its worker
// sockets with a worker: the synchronous send looks no table up while
// this is zero (every engine that never sent asynchronously)
std::atomic<int> g_egress_count{0};

EgressWorker *egress_of(int fd) {
  if (g_egress_count.load(std::memory_order_acquire) == 0) return nullptr;
  std::lock_guard<std::mutex> g(g_egress_mu);
  auto it = g_egress.find(fd);
  return it == g_egress.end() ? nullptr : it->second;
}

// the order barrier of the synchronous calls, and udp_send_flush
void egress_wait_idle(EgressWorker *w) {
  std::unique_lock<std::mutex> lk(w->mu);
  w->room.wait(lk, [w] { return w->idle(); });
}

}  // namespace

extern "C" {

// Create a bound UDP socket.  reuseport != 0 enables SO_REUSEPORT so N
// engine instances can share one port (kernel-level stream sharding).
// Returns fd >= 0 or -errno.
int udp_create(const char *bind_ip, uint16_t port, int reuseport,
               int rcvbuf_bytes) {
  int fd = socket(AF_INET, SOCK_DGRAM | SOCK_NONBLOCK, 0);
  if (fd < 0) return -errno;
  int one = 1;
  setsockopt(fd, SOL_SOCKET, SO_REUSEADDR, &one, sizeof(one));
  if (reuseport) setsockopt(fd, SOL_SOCKET, SO_REUSEPORT, &one, sizeof(one));
  if (rcvbuf_bytes > 0)
    setsockopt(fd, SOL_SOCKET, SO_RCVBUF, &rcvbuf_bytes, sizeof(rcvbuf_bytes));
  sockaddr_in addr{};
  addr.sin_family = AF_INET;
  addr.sin_port = htons(port);
  addr.sin_addr.s_addr = bind_ip ? inet_addr(bind_ip) : INADDR_ANY;
  if (bind(fd, reinterpret_cast<sockaddr *>(&addr), sizeof(addr)) < 0) {
    int e = errno;
    close(fd);
    return -e;
  }
  return fd;
}

// Close the socket.  Where it has an egress worker: everything handed
// over is sent first, then the worker is joined (completions nobody
// reaped go with it).
int udp_close(int fd) {
  EgressWorker *w = nullptr;
  if (g_egress_count.load(std::memory_order_acquire) != 0) {
    std::lock_guard<std::mutex> g(g_egress_mu);
    auto it = g_egress.find(fd);
    if (it != g_egress.end()) {
      w = it->second;
      g_egress.erase(it);
      g_egress_count.fetch_sub(1, std::memory_order_release);
    }
  }
  if (w) {
    {
      std::lock_guard<std::mutex> g(w->mu);
      w->stop = true;
    }
    w->work.notify_all();
    w->thread.join();
    delete w;
  }
  return close(fd);
}

// Enable kernel receive timestamps (SO_TIMESTAMPNS).  The BWE
// inter-arrival filters (GCC) react to sub-millisecond queueing-delay
// gradients; userspace arrival times include scheduler jitter that the
// kernel stamp (taken at skb receive) does not.  Returns 0 or -errno.
int udp_enable_timestamps(int fd) {
  int one = 1;
  if (setsockopt(fd, SOL_SOCKET, SO_TIMESTAMPNS, &one, sizeof(one)) < 0)
    return -errno;
  return 0;
}

// Get the locally bound port (for port-0 ephemeral binds in tests).
int udp_local_port(int fd) {
  sockaddr_in addr{};
  socklen_t len = sizeof(addr);
  if (getsockname(fd, reinterpret_cast<sockaddr *>(&addr), &len) < 0)
    return -errno;
  return ntohs(addr.sin_port);
}

// (see udp_recv_batch_ts below; this entry point keeps the original
// ABI and simply skips the timestamp plumbing)
int udp_recv_batch_ts(int fd, uint8_t *buf, int capacity, int max_pkts,
                      int32_t *lengths, uint32_t *src_ip,
                      uint16_t *src_port, int64_t *arrival_ns,
                      int timeout_ms);

// Batched receive via recvmmsg into the caller's [max_pkts, capacity]
// row-major buffer; writes per-packet lengths, source ip4 (host order)
// and ports.  Waits up to timeout_ms for the FIRST packet, then drains
// whatever is immediately available (the batching-window pattern: the
// caller controls latency by the timeout, throughput by max_pkts).
// Returns number of packets, 0 on timeout, -errno on error.
int udp_recv_batch(int fd, uint8_t *buf, int capacity, int max_pkts,
                   int32_t *lengths, uint32_t *src_ip, uint16_t *src_port,
                   int timeout_ms) {
  return udp_recv_batch_ts(fd, buf, capacity, max_pkts, lengths, src_ip,
                           src_port, nullptr, timeout_ms);
}

// Timestamped batched receive: like udp_recv_batch, and when
// arrival_ns != nullptr also writes per-packet kernel arrival times
// (CLOCK_REALTIME nanoseconds).  Packets without a kernel stamp
// (SO_TIMESTAMPNS not enabled / not delivered) fall back to a
// syscall-time clock_gettime taken once per batch.
//
// After the first recvmmsg a busy-poll drain pass keeps calling
// recvmmsg(MSG_DONTWAIT) into the remaining rows while datagrams are
// still queued, so a burst that straddles the first syscall fills the
// batch instead of spilling into the next tick.  The drain is bounded
// by max_pkts — it never spins on an idle socket.
int udp_recv_batch_ts(int fd, uint8_t *buf, int capacity, int max_pkts,
                      int32_t *lengths, uint32_t *src_ip,
                      uint16_t *src_port, int64_t *arrival_ns,
                      int timeout_ms) {
  if (timeout_ms > 0) {
    pollfd p{fd, POLLIN, 0};
    int pr = poll(&p, 1, timeout_ms);
    if (pr < 0) return -errno;
    if (pr == 0) return 0;
  }
  // hoisted per-call scratch: the tick loop calls this at high rate and
  // the header/iov arrays are identical shape every time
  thread_local std::vector<mmsghdr> hdrs;
  thread_local std::vector<iovec> iovs;
  thread_local std::vector<sockaddr_in> addrs;
  thread_local std::vector<uint8_t> ctrl;
  if (static_cast<int>(hdrs.size()) < max_pkts) {
    hdrs.resize(max_pkts);
    iovs.resize(max_pkts);
    addrs.resize(max_pkts);
  }
  constexpr size_t kCtrl = 64;  // room for one timestampns cmsg
  if (arrival_ns &&
      ctrl.size() < static_cast<size_t>(max_pkts) * kCtrl)
    ctrl.resize(static_cast<size_t>(max_pkts) * kCtrl);
  for (int i = 0; i < max_pkts; i++) {
    iovs[i].iov_base = buf + static_cast<size_t>(i) * capacity;
    iovs[i].iov_len = capacity;
    std::memset(&hdrs[i], 0, sizeof(mmsghdr));
    hdrs[i].msg_hdr.msg_iov = &iovs[i];
    hdrs[i].msg_hdr.msg_iovlen = 1;
    hdrs[i].msg_hdr.msg_name = &addrs[i];
    hdrs[i].msg_hdr.msg_namelen = sizeof(sockaddr_in);
    if (arrival_ns) {
      hdrs[i].msg_hdr.msg_control =
          ctrl.data() + static_cast<size_t>(i) * kCtrl;
      hdrs[i].msg_hdr.msg_controllen = kCtrl;
    }
  }
  int total = 0;
  while (total < max_pkts) {
    int want = max_pkts - total;
    int n = recvmmsg(fd, hdrs.data() + total, want, MSG_DONTWAIT, nullptr);
    if (n < 0) {
      if (errno == EAGAIN || errno == EWOULDBLOCK) break;
      if (total > 0) break;  // deliver what we have; error next call
      return -errno;
    }
    if (n == 0) break;
    // a short return means the queue emptied mid-call — but datagrams
    // may have landed during the copy, so go around again and let
    // EAGAIN (not the short count) terminate the drain
    total += n;
  }
  if (total == 0) return 0;
  int64_t fallback = 0;
  if (arrival_ns) {
    timespec now{};
    clock_gettime(CLOCK_REALTIME, &now);
    fallback = static_cast<int64_t>(now.tv_sec) * 1000000000LL + now.tv_nsec;
  }
  for (int i = 0; i < total; i++) {
    lengths[i] = static_cast<int32_t>(hdrs[i].msg_len);
    src_ip[i] = ntohl(addrs[i].sin_addr.s_addr);
    src_port[i] = ntohs(addrs[i].sin_port);
    if (!arrival_ns) continue;
    arrival_ns[i] = fallback;
    for (cmsghdr *c = CMSG_FIRSTHDR(&hdrs[i].msg_hdr); c;
         c = CMSG_NXTHDR(&hdrs[i].msg_hdr, c)) {
      if (c->cmsg_level == SOL_SOCKET && c->cmsg_type == SO_TIMESTAMPNS) {
        timespec ts{};
        std::memcpy(&ts, CMSG_DATA(c), sizeof(ts));
        arrival_ns[i] =
            static_cast<int64_t>(ts.tv_sec) * 1000000000LL + ts.tv_nsec;
        break;
      }
    }
  }
  return total;
}

// Row-indexed gather send via sendmmsg.  Rows are selected by idx[]
// into the caller's full [*, capacity] row-major matrix, so the host
// never materializes a contiguous copy of the egress subset: the iovec
// gather IS the row selection, and the whole multi-destination burst
// is one syscall (per-msg msg_name carries each row's destination).
// lengths/dst_ip/dst_port are length-n arrays in idx order; idx may be
// nullptr for the identity (rows 0..n-1).  dst_ip is host-order ip4.
// Returns packets sent or -errno.
int udp_send_batch_idx(int fd, const uint8_t *buf, int capacity,
                       const int32_t *lengths, const uint32_t *dst_ip,
                       const uint16_t *dst_port, const int32_t *idx,
                       int n) {
  // behind whatever was handed to the socket's worker, inline when
  // nothing was (and always where the socket has no worker)
  if (EgressWorker *w = egress_of(fd)) egress_wait_idle(w);
  return send_burst(fd, buf, capacity, lengths, dst_ip, dst_port, idx, n);
}

// Batched send via sendmmsg from the same row-major layout.
// dst_ip is host-order ip4.  Returns packets sent or -errno.
int udp_send_batch(int fd, const uint8_t *buf, int capacity,
                   const int32_t *lengths, const uint32_t *dst_ip,
                   const uint16_t *dst_port, int n) {
  return udp_send_batch_idx(fd, buf, capacity, lengths, dst_ip, dst_port,
                            nullptr, n);
}

// Hand rows 0..n-1 to the socket's egress worker and return at once:
// a job id > 0, or -errno (the worker could not be started).  Waits
// only while kEgressQueueJobs jobs are already queued.  `behind` (may
// be nullptr) is set to 1 when a job handed over earlier had not
// completed yet, else 0.  The arrays must stay alive and unwritten
// until the job's completion is reaped.
int64_t udp_send_async(int fd, const uint8_t *buf, int capacity,
                       const int32_t *lengths, const uint32_t *dst_ip,
                       const uint16_t *dst_port, int n, int *behind) {
  EgressWorker *w = egress_of(fd);
  if (!w) {
    std::lock_guard<std::mutex> g(g_egress_mu);
    auto it = g_egress.find(fd);
    if (it != g_egress.end()) {
      w = it->second;
    } else {
      w = new (std::nothrow) EgressWorker();
      if (!w) return -ENOMEM;
      w->fd = fd;
      try {
        w->thread = std::thread(&EgressWorker::run, w);
      } catch (const std::system_error &e) {
        delete w;
        return -(e.code().value() > 0 ? e.code().value() : EAGAIN);
      }
      g_egress[fd] = w;
      g_egress_count.fetch_add(1, std::memory_order_release);
    }
  }
  std::unique_lock<std::mutex> lk(w->mu);
  if (behind) *behind = w->idle() ? 0 : 1;
  w->room.wait(lk, [w] {
    return static_cast<int>(w->queue.size()) < kEgressQueueJobs;
  });
  int64_t id = w->next_id++;
  w->queue.push_back(EgressJob{id, buf, capacity, lengths, dst_ip,
                               dst_port, n});
  lk.unlock();
  w->work.notify_one();
  return id;
}

// Completions so far, oldest first, at most `max`; never blocks.
// Writes job id, `sent` or -errno, and the CLOCK_MONOTONIC ns at which
// the worker began and ended the job's sendmmsg loop.  Each completion
// is returned once.  Returns how many were written.
int udp_send_reap(int fd, int64_t *ids, int32_t *sent, int64_t *t0_ns,
                  int64_t *t1_ns, int max) {
  EgressWorker *w = egress_of(fd);
  if (!w) return 0;
  std::lock_guard<std::mutex> g(w->mu);
  int k = 0;
  while (k < max && !w->done.empty()) {
    const EgressDone &d = w->done.front();
    ids[k] = d.id;
    sent[k] = d.sent;
    t0_ns[k] = d.t0_ns;
    t1_ns[k] = d.t1_ns;
    w->done.pop_front();
    k++;
  }
  return k;
}

// Wait until everything handed over has been sent (completions stay
// to be reaped).  Returns 0.
int udp_send_flush(int fd) {
  if (EgressWorker *w = egress_of(fd)) egress_wait_idle(w);
  return 0;
}

}  // extern "C"

// ===========================================================================
// io_uring engine (generation 2 host I/O).
//
// Same socket, same pinned-arena memory contract as the recvmmsg engine
// above, but ingest is ring-driven: every row of the CURRENT recv arena
// gets a single-shot RECVMSG SQE whose iovec points at that row, the
// whole arena is armed with ONE io_uring_enter, and steady-state drains
// reap completions from the shared-memory CQ without entering the
// kernel at all.  One syscall then covers an entire arena fill-cycle
// (rows packets) instead of one per recvmmsg window.
//
// Deliberate non-use of multishot RECVMSG: multishot completions carry
// an io_uring_recvmsg_out header + name/control blob IN the data
// buffer, in completion order from a provided-buffer pool — both break
// the arena contract (payload bytes at row offset 0, rows contiguous
// in arrival order) that makes the recv arena a zero-copy PacketBatch.
// Re-armed single-shot RECVMSG keeps the exact memory layout and still
// amortizes the enter down to ~1/rows per packet, which is what the
// syscall telemetry (udp_uring_stat) lets callers verify.
//
// Delivery is CONTIGUOUS-PREFIX: completions can land out of row order
// (rarely, under load), so a drain hands back only the completed prefix
// [delivered, first-hole) and later calls pick up the rest.  Egress
// is not the ring's: every send is a sendmmsg (above), the fan-out's
// on the socket's egress worker.
//
// Built only when the kernel UAPI header is present; otherwise every
// entry point is an ENOSYS stub so one .so serves both worlds and the
// Python probe (udp_uring_supported) picks the engine at runtime.

#if defined(__linux__) && defined(HAVE_IO_URING)

#include <linux/io_uring.h>
#include <linux/time_types.h>
#include <sys/mman.h>
#include <sys/syscall.h>

// cancel-any postdates some UAPI headers (kernel 5.19); the running
// kernel decides support at runtime, the constant is ABI-stable
#ifndef IORING_ASYNC_CANCEL_ANY
#define IORING_ASYNC_CANCEL_ANY (1U << 2)
#endif

namespace {

int sys_uring_setup(unsigned entries, io_uring_params *p) {
  return static_cast<int>(syscall(__NR_io_uring_setup, entries, p));
}

int sys_uring_enter(int fd, unsigned to_submit, unsigned min_complete,
                    unsigned flags, const void *arg, size_t argsz) {
  return static_cast<int>(syscall(__NR_io_uring_enter, fd, to_submit,
                                  min_complete, flags, arg, argsz));
}

constexpr uint64_t kCancelTag = 1ULL << 62;  // user_data: not a recv row

struct UringEngine {
  int sock_fd = -1;
  int ring_fd = -1;
  unsigned features = 0;
  bool sqpoll = false;
  bool want_ts = false;
  // mmapped ring state
  void *sq_ptr = nullptr, *cq_ptr = nullptr;
  size_t sq_len = 0, cq_len = 0, sqe_len = 0;
  io_uring_sqe *sqes = nullptr;
  unsigned *sq_head = nullptr, *sq_tail = nullptr, *sq_mask = nullptr;
  unsigned *sq_flags = nullptr, *sq_array = nullptr;
  unsigned *cq_head = nullptr, *cq_tail = nullptr, *cq_mask = nullptr;
  io_uring_cqe *cqes = nullptr;
  unsigned sq_entries = 0, cq_entries = 0;
  unsigned sq_pending = 0;  // SQEs staged since the last submit
  // current arena (one fill-cycle): metadata written straight into the
  // caller's arena-backed arrays at absolute row positions
  uint8_t *buf = nullptr;
  int rows = 0, capacity = 0;
  int32_t *out_len = nullptr;
  uint32_t *out_ip = nullptr;
  uint16_t *out_port = nullptr;
  int64_t *out_ts = nullptr;
  int posted = 0;     // rows with an SQE armed (staged or submitted)
  int delivered = 0;  // contiguous prefix handed back to the caller
  int inflight = 0;   // armed, not yet completed
  std::vector<uint8_t> completed;    // per-row completion flag
  std::vector<msghdr> mh;            // per-row op resources: must stay
  std::vector<iovec> iov;            // alive until the CQE arrives
  std::vector<sockaddr_in> addr;
  std::vector<uint8_t> ctrl;
  long enters = 0;      // io_uring_enter syscalls (the honest count)
  long reaps = 0;       // completions consumed ring-side
  long recv_errors = 0; // failed recv completions (row re-armed)
};

constexpr size_t kUringCtrl = 64;  // room for one timestampns cmsg

unsigned npow2(unsigned v) {
  unsigned p = 1;
  while (p < v) p <<= 1;
  return p;
}

int64_t cmsg_stamp(msghdr *m, int64_t fallback) {
  for (cmsghdr *c = CMSG_FIRSTHDR(m); c; c = CMSG_NXTHDR(m, c)) {
    if (c->cmsg_level == SOL_SOCKET && c->cmsg_type == SO_TIMESTAMPNS) {
      timespec ts{};
      std::memcpy(&ts, CMSG_DATA(c), sizeof(ts));
      return static_cast<int64_t>(ts.tv_sec) * 1000000000LL + ts.tv_nsec;
    }
  }
  return fallback;
}

// stage one SQE (caller guarantees SQ room); submission happens later
io_uring_sqe *stage_sqe(UringEngine *u) {
  unsigned tail = *u->sq_tail + u->sq_pending;
  io_uring_sqe *sqe = &u->sqes[tail & *u->sq_mask];
  std::memset(sqe, 0, sizeof(*sqe));
  u->sq_array[tail & *u->sq_mask] = tail & *u->sq_mask;
  u->sq_pending++;
  return sqe;
}

unsigned sq_room(UringEngine *u) {
  unsigned head = __atomic_load_n(u->sq_head, __ATOMIC_ACQUIRE);
  unsigned used = (*u->sq_tail + u->sq_pending) - head;
  return u->sq_entries - used;
}

// publish staged SQEs and optionally wait for >=1 completion.  The
// only place the engine enters the kernel.
int uring_submit(UringEngine *u, bool wait, int timeout_ms) {
  unsigned to_submit = u->sq_pending;
  if (to_submit) {
    __atomic_store_n(u->sq_tail, *u->sq_tail + to_submit,
                     __ATOMIC_RELEASE);
    u->sq_pending = 0;
  }
  unsigned flags = 0;
  unsigned min_complete = 0;
  io_uring_getevents_arg arg{};
  __kernel_timespec kts{};
  const void *argp = nullptr;
  size_t argsz = 0;
  if (wait) {
    flags |= IORING_ENTER_GETEVENTS;
    min_complete = 1;
    if (timeout_ms >= 0 && (u->features & IORING_FEAT_EXT_ARG)) {
      kts.tv_sec = timeout_ms / 1000;
      kts.tv_nsec = static_cast<long long>(timeout_ms % 1000) * 1000000;
      arg.ts = reinterpret_cast<uint64_t>(&kts);
      argp = &arg;
      argsz = sizeof(arg);
      flags |= IORING_ENTER_EXT_ARG;
    }
  }
  if (u->sqpoll) {
    unsigned sf = __atomic_load_n(u->sq_flags, __ATOMIC_ACQUIRE);
    if (!wait && !(sf & IORING_SQ_NEED_WAKEUP)) return 0;  // no syscall
    if (sf & IORING_SQ_NEED_WAKEUP) flags |= IORING_ENTER_SQ_WAKEUP;
    to_submit = 0;  // the poller thread consumes the SQ itself
  } else if (!to_submit && !wait) {
    return 0;
  }
  u->enters++;
  int r = sys_uring_enter(u->ring_fd, to_submit, min_complete, flags,
                          argp, argsz);
  if (r < 0 && errno != ETIME && errno != EINTR && errno != EBUSY)
    return -errno;
  return 0;
}

// Arm RECVMSG SQEs for every not-yet-posted row, as ONE IOSQE_IO_LINK
// chain: the kernel starts recv i+1 only after recv i completes.  The
// chain (a) preserves arrival order across rows — the arena stays a
// time-ordered batch exactly like the recvmmsg engine's, so the accept
// set can be bit-identical across engine modes, and (b) keeps a single
// poll waiter on the socket instead of rows-many (independent armed
// recvs race their poll retries, scrambling packet->row assignment and
// thundering-herd-waking every waiter per datagram).  A queued burst
// still cascades down the chain entirely in-kernel, zero syscalls.
//
// Guarded on inflight == 0: rows only (re-)arm when no prior SQE is
// outstanding, so a failed chain (one error cancels the remaining
// links) is re-armed as one fresh chain AFTER all its -ECANCELED
// completions drain — a row is never double-armed.
void arm_rows(UringEngine *u) {
  if (u->inflight > 0 || u->posted >= u->rows) return;
  io_uring_sqe *last = nullptr;
  while (u->posted < u->rows && sq_room(u) > 0) {
    int row = u->posted;
    u->iov[row].iov_base = u->buf + static_cast<size_t>(row) * u->capacity;
    u->iov[row].iov_len = u->capacity;
    std::memset(&u->mh[row], 0, sizeof(msghdr));
    u->mh[row].msg_iov = &u->iov[row];
    u->mh[row].msg_iovlen = 1;
    u->mh[row].msg_name = &u->addr[row];
    u->mh[row].msg_namelen = sizeof(sockaddr_in);
    if (u->want_ts) {
      u->mh[row].msg_control = u->ctrl.data() + row * kUringCtrl;
      u->mh[row].msg_controllen = kUringCtrl;
    }
    io_uring_sqe *sqe = stage_sqe(u);
    sqe->opcode = IORING_OP_RECVMSG;
    sqe->flags = IOSQE_IO_LINK;
    sqe->fd = u->sock_fd;
    sqe->addr = reinterpret_cast<uint64_t>(&u->mh[row]);
    sqe->user_data = static_cast<uint64_t>(row);
    u->posted++;
    u->inflight++;
    last = sqe;
  }
  if (last) last->flags &= ~IOSQE_IO_LINK;  // terminate the chain
}

// drain the CQ ring-side (no syscall).  Recv completions mark their
// row done and stash metadata into the arena arrays; failed recvs
// (e.g. ECONNREFUSED surfacing a prior send's ICMP error) re-arm the
// row.  The teardown's cancel op (kCancelTag) is no row.  Returns number
// of completions consumed.
int reap(UringEngine *u) {
  int n = 0;
  int64_t fallback = 0;
  unsigned head = *u->cq_head;
  for (;;) {
    unsigned tail = __atomic_load_n(u->cq_tail, __ATOMIC_ACQUIRE);
    if (head == tail) break;
    io_uring_cqe *cqe = &u->cqes[head & *u->cq_mask];
    uint64_t ud = cqe->user_data;
    int res = cqe->res;
    head++;
    n++;
    if (!(ud & kCancelTag)) {
      int row = static_cast<int>(ud);
      u->inflight--;
      if (res < 0) {
        // chain-head error (e.g. ECONNREFUSED surfacing a prior
        // send's ICMP error) or the -ECANCELED tail the failed link
        // cascaded: roll `posted` back to the first affected row.
        // arm_rows re-arms the contiguous suffix as one fresh chain
        // once every outstanding completion has drained (inflight 0),
        // so ordering and the never-double-armed invariant both hold.
        if (res != -ECANCELED) u->recv_errors++;
        if (row < u->posted) u->posted = row;
        continue;
      }
      u->completed[row] = 1;
      u->out_len[row] = res;  // truncated to capacity, recvmmsg-style
      u->out_ip[row] = ntohl(u->addr[row].sin_addr.s_addr);
      u->out_port[row] = ntohs(u->addr[row].sin_port);
      if (u->out_ts) {
        if (fallback == 0) {
          timespec now{};
          clock_gettime(CLOCK_REALTIME, &now);
          fallback = static_cast<int64_t>(now.tv_sec) * 1000000000LL +
                     now.tv_nsec;
        }
        u->out_ts[row] = cmsg_stamp(&u->mh[row], fallback);
      }
    }
  }
  if (n) {
    __atomic_store_n(u->cq_head, head, __ATOMIC_RELEASE);
    u->reaps += n;
  }
  return n;
}

}  // namespace

extern "C" {

#define URING_ARENA_EXHAUSTED (-9999)

// Runtime probe: can this kernel set up an io_uring at all?  Cached.
int udp_uring_supported(void) {
  static int cached = -1;
  if (cached >= 0) return cached;
  io_uring_params p{};
  int fd = sys_uring_setup(4, &p);
  if (fd >= 0) {
    close(fd);
    cached = 1;
  } else {
    cached = 0;
  }
  return cached;
}

// Create a ring bound to an existing UDP socket (from udp_create).
// `entries` sizes the arena (rows) the ring must cover; the CQ is
// sized for a full arena of recv completions plus an egress burst.
// Returns an opaque handle or nullptr.
void *udp_uring_create(int sock_fd, int entries, int sqpoll, int want_ts) {
  UringEngine *u = new (std::nothrow) UringEngine();
  if (!u) return nullptr;
  unsigned sq = npow2(static_cast<unsigned>(entries < 8 ? 8 : entries));
  if (sq > 4096) sq = 4096;
  io_uring_params p{};
  p.flags = IORING_SETUP_CQSIZE;
  p.cq_entries = sq * 2;
  if (sqpoll) {
    p.flags |= IORING_SETUP_SQPOLL;
    p.sq_thread_idle = 100;
  }
  int rfd = sys_uring_setup(sq, &p);
  if (rfd < 0 && sqpoll) {
    // SQPOLL can need privileges older kernels reserve; fall back to
    // the enter-per-submit mode rather than failing the engine
    p.flags = IORING_SETUP_CQSIZE;
    sqpoll = 0;
    rfd = sys_uring_setup(sq, &p);
  }
  if (rfd < 0) {
    delete u;
    return nullptr;
  }
  u->sock_fd = sock_fd;
  u->ring_fd = rfd;
  u->features = p.features;
  u->sqpoll = sqpoll != 0;
  u->want_ts = want_ts != 0;
  u->sq_entries = p.sq_entries;
  u->cq_entries = p.cq_entries;
  u->sq_len = p.sq_off.array + p.sq_entries * sizeof(unsigned);
  u->cq_len = p.cq_off.cqes + p.cq_entries * sizeof(io_uring_cqe);
  if (p.features & IORING_FEAT_SINGLE_MMAP) {
    size_t len = u->sq_len > u->cq_len ? u->sq_len : u->cq_len;
    u->sq_ptr = mmap(nullptr, len, PROT_READ | PROT_WRITE,
                     MAP_SHARED | MAP_POPULATE, rfd, IORING_OFF_SQ_RING);
    u->cq_ptr = u->sq_ptr;
    u->sq_len = u->cq_len = len;
  } else {
    u->sq_ptr = mmap(nullptr, u->sq_len, PROT_READ | PROT_WRITE,
                     MAP_SHARED | MAP_POPULATE, rfd, IORING_OFF_SQ_RING);
    u->cq_ptr = mmap(nullptr, u->cq_len, PROT_READ | PROT_WRITE,
                     MAP_SHARED | MAP_POPULATE, rfd, IORING_OFF_CQ_RING);
  }
  u->sqe_len = p.sq_entries * sizeof(io_uring_sqe);
  u->sqes = static_cast<io_uring_sqe *>(
      mmap(nullptr, u->sqe_len, PROT_READ | PROT_WRITE,
           MAP_SHARED | MAP_POPULATE, rfd, IORING_OFF_SQES));
  if (u->sq_ptr == MAP_FAILED || u->cq_ptr == MAP_FAILED ||
      u->sqes == MAP_FAILED) {
    close(rfd);
    delete u;
    return nullptr;
  }
  auto *sqb = static_cast<uint8_t *>(u->sq_ptr);
  u->sq_head = reinterpret_cast<unsigned *>(sqb + p.sq_off.head);
  u->sq_tail = reinterpret_cast<unsigned *>(sqb + p.sq_off.tail);
  u->sq_mask = reinterpret_cast<unsigned *>(sqb + p.sq_off.ring_mask);
  u->sq_flags = reinterpret_cast<unsigned *>(sqb + p.sq_off.flags);
  u->sq_array = reinterpret_cast<unsigned *>(sqb + p.sq_off.array);
  auto *cqb = static_cast<uint8_t *>(u->cq_ptr);
  u->cq_head = reinterpret_cast<unsigned *>(cqb + p.cq_off.head);
  u->cq_tail = reinterpret_cast<unsigned *>(cqb + p.cq_off.tail);
  u->cq_mask = reinterpret_cast<unsigned *>(cqb + p.cq_off.ring_mask);
  u->cqes = reinterpret_cast<io_uring_cqe *>(cqb + p.cq_off.cqes);
  return u;
}

// Hand the ring a fresh arena to fill (one fill-cycle = rows packets).
// Per-row metadata is written straight into the arena-backed arrays at
// absolute row positions as completions arrive.  Fails with -EBUSY
// while recvs from the previous arena are still in flight — callers
// switch arenas only at exhaustion, where inflight is naturally 0, so
// the kernel NEVER holds a reference into a handed-back arena.
int udp_uring_arm(void *h, uint8_t *buf, int rows, int capacity,
                  int32_t *lengths, uint32_t *src_ip, uint16_t *src_port,
                  int64_t *arrival_ns) {
  auto *u = static_cast<UringEngine *>(h);
  if (!u || rows <= 0) return -EINVAL;
  reap(u);
  if (u->inflight > 0) return -EBUSY;
  if (static_cast<unsigned>(rows) > u->sq_entries) rows = u->sq_entries;
  u->buf = buf;
  u->rows = rows;
  u->capacity = capacity;
  u->out_len = lengths;
  u->out_ip = src_ip;
  u->out_port = src_port;
  u->out_ts = arrival_ns;
  u->posted = 0;
  u->delivered = 0;
  u->completed.assign(rows, 0);
  if (static_cast<int>(u->mh.size()) < rows) {
    u->mh.resize(rows);
    u->iov.resize(rows);
    u->addr.resize(rows);
  }
  if (u->want_ts && u->ctrl.size() < rows * kUringCtrl)
    u->ctrl.resize(rows * kUringCtrl);
  arm_rows(u);
  return uring_submit(u, false, 0);  // one enter arms the whole arena
}

// Deliver up to max_pkts completed packets as a CONTIGUOUS row run.
// Writes the first delivered row to *start_row; returns the count
// (0 on timeout), URING_ARENA_EXHAUSTED when every row of the current
// arena has been delivered (caller arms the next arena), or -errno.
// Steady state (completions already waiting) never enters the kernel.
int udp_uring_recv(void *h, int max_pkts, int timeout_ms,
                   int32_t *start_row) {
  auto *u = static_cast<UringEngine *>(h);
  if (!u || !u->buf) return -EINVAL;
  if (u->delivered >= u->rows) return URING_ARENA_EXHAUSTED;
  reap(u);
  arm_rows(u);
  if (u->sq_pending) uring_submit(u, false, 0);
  if (!u->completed[u->delivered] && timeout_ms > 0) {
    int r = uring_submit(u, true, timeout_ms);
    if (r < 0) return r;
    reap(u);
  }
  int lo = u->delivered;
  int hi = lo;
  int cap = lo + (max_pkts < u->rows - lo ? max_pkts : u->rows - lo);
  while (hi < cap && u->completed[hi]) hi++;
  if (hi == lo) return 0;
  u->delivered = hi;
  *start_row = lo;
  return hi - lo;
}

// Telemetry: 0 = io_uring_enter syscalls, 1 = completions reaped
// ring-side, 2 = SQPOLL active, 3 = failed recv completions re-armed.
long udp_uring_stat(void *h, int which) {
  auto *u = static_cast<UringEngine *>(h);
  if (!u) return -EINVAL;
  switch (which) {
    case 0: return u->enters;
    case 1: return u->reaps;
    case 2: return u->sqpoll ? 1 : 0;
    case 3: return u->recv_errors;
  }
  return -EINVAL;
}

// Tear down the ring.  Armed recvs hold kernel references into the
// per-row msghdr slots (and the caller's arena), so they are cancelled
// (IORING_OP_ASYNC_CANCEL, cancel-any) and their completions drained
// BEFORE anything is freed — closing the ring fd alone defers the
// kernel-side cancellation and would race the frees.  If the drain
// cannot converge the engine struct is deliberately leaked rather than
// handing the kernel dangling memory.  Does NOT close sock_fd.
void udp_uring_destroy(void *h) {
  auto *u = static_cast<UringEngine *>(h);
  if (!u) return;
  if (u->inflight > 0 && u->ring_fd >= 0) {
    io_uring_sqe *sqe = stage_sqe(u);
    sqe->opcode = IORING_OP_ASYNC_CANCEL;
    sqe->cancel_flags = IORING_ASYNC_CANCEL_ANY;
    sqe->user_data = kCancelTag | 1;
    uring_submit(u, false, 0);
    for (int i = 0; i < 64 && u->inflight > 0; i++) {
      reap(u);
      if (u->inflight > 0 && uring_submit(u, true, 50) < 0) break;
    }
    reap(u);
    if (u->inflight > 0) {
      close(u->ring_fd);  // leak u: kernel may still reference mh[]
      return;
    }
  }
  if (u->sqes && u->sqes != MAP_FAILED) munmap(u->sqes, u->sqe_len);
  if (u->cq_ptr && u->cq_ptr != u->sq_ptr && u->cq_ptr != MAP_FAILED)
    munmap(u->cq_ptr, u->cq_len);
  if (u->sq_ptr && u->sq_ptr != MAP_FAILED) munmap(u->sq_ptr, u->sq_len);
  if (u->ring_fd >= 0) close(u->ring_fd);
  delete u;
}

}  // extern "C"

#else  // !HAVE_IO_URING ------------------------------------------------

// ENOSYS stubs: the one .so serves kernels/toolchains without io_uring;
// the Python probe sees udp_uring_supported() == 0 and stays on the
// recvmmsg engine with a bit-identical accept set.
extern "C" {

int udp_uring_supported(void) { return 0; }

void *udp_uring_create(int, int, int, int) { return nullptr; }

int udp_uring_arm(void *, uint8_t *, int, int, int32_t *, uint32_t *,
                  uint16_t *, int64_t *) {
  return -ENOSYS;
}

int udp_uring_recv(void *, int, int, int32_t *) { return -ENOSYS; }

long udp_uring_stat(void *, int) { return -ENOSYS; }

void udp_uring_destroy(void *) {}

}  // extern "C"

#endif  // HAVE_IO_URING
