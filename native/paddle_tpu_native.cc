// Native runtime components for paddle_tpu (C ABI, loaded via ctypes).
//
// TPU-native equivalents of the reference's native data-path pieces:
//  - BlockingQueue: bounded MPMC byte-buffer queue feeding the device input
//    pipeline (reference: operators/reader/lod_tensor_blocking_queue.h and
//    the double-buffer reader's staging queue).
//  - RecordIO: chunked record file format with per-chunk CRC32 and optional
//    zlib compression (reference: paddle/fluid/recordio/{header,chunk,
//    scanner,writer} — same structure: magic, per-chunk record count,
//    compressor tag, checksum).
//  - ThreadPool: fixed worker pool used by the host-side pipeline
//    (reference: framework/threadpool.h).
//
// Build: make -C native   (g++ -O2 -fPIC -shared -lz -lpthread)

#include <atomic>
#include <condition_variable>
#include <cstdint>
#include <cstdio>
#include <cstring>
#include <deque>
#include <functional>
#include <mutex>
#include <string>
#include <thread>
#include <vector>

#include <zlib.h>

#include <arpa/inet.h>
#include <netinet/in.h>
#include <netinet/tcp.h>
#include <sys/socket.h>
#include <sys/uio.h>
#include <unistd.h>
#include <cerrno>
#include <chrono>

extern "C" {

// ---------------------------------------------------------------------------
// BlockingQueue of byte buffers
// ---------------------------------------------------------------------------

struct Queue {
  size_t capacity;
  std::deque<std::string> items;
  std::mutex mu;
  std::condition_variable not_full, not_empty;
  bool closed = false;
};

void* ptq_queue_create(size_t capacity) {
  auto* q = new Queue();
  q->capacity = capacity ? capacity : 1;
  return q;
}

// blocks while full; returns 0 on success, -1 if closed
int ptq_queue_push(void* qp, const char* data, size_t len) {
  auto* q = static_cast<Queue*>(qp);
  std::unique_lock<std::mutex> lk(q->mu);
  q->not_full.wait(lk, [q] { return q->items.size() < q->capacity || q->closed; });
  if (q->closed) return -1;
  q->items.emplace_back(data, len);
  q->not_empty.notify_one();
  return 0;
}

// blocks while empty; returns length (malloc'd into *out), -1 if closed+drained
long ptq_queue_pop(void* qp, char** out) {
  auto* q = static_cast<Queue*>(qp);
  std::unique_lock<std::mutex> lk(q->mu);
  q->not_empty.wait(lk, [q] { return !q->items.empty() || q->closed; });
  if (q->items.empty()) return -1;
  std::string s = std::move(q->items.front());
  q->items.pop_front();
  q->not_full.notify_one();
  lk.unlock();
  *out = static_cast<char*>(malloc(s.size()));
  memcpy(*out, s.data(), s.size());
  return static_cast<long>(s.size());
}

void ptq_buffer_free(char* buf) { free(buf); }

void ptq_queue_close(void* qp) {
  auto* q = static_cast<Queue*>(qp);
  std::lock_guard<std::mutex> lk(q->mu);
  q->closed = true;
  q->not_empty.notify_all();
  q->not_full.notify_all();
}

size_t ptq_queue_size(void* qp) {
  auto* q = static_cast<Queue*>(qp);
  std::lock_guard<std::mutex> lk(q->mu);
  return q->items.size();
}

int ptq_queue_closed(void* qp) {
  auto* q = static_cast<Queue*>(qp);
  std::lock_guard<std::mutex> lk(q->mu);
  return q->closed ? 1 : 0;
}

void ptq_queue_destroy(void* qp) { delete static_cast<Queue*>(qp); }

// ---------------------------------------------------------------------------
// RecordIO (recordio/header.h:25 layout concept: chunked, CRC, compressor)
// ---------------------------------------------------------------------------

static const uint32_t kMagic = 0x50545152;  // "PTQR"
enum Compressor { kNone = 0, kZlib = 1 };

struct ChunkHeader {
  uint32_t magic;
  uint32_t num_records;
  uint32_t compressor;
  uint32_t crc32;
  uint64_t payload_len;  // on-disk (possibly compressed) length
};

struct Writer {
  FILE* f;
  int compressor;
  size_t max_records;
  std::string buf;       // raw concatenated (len,data) records
  uint32_t num_records = 0;
};

static int write_chunk(Writer* w) {
  if (w->num_records == 0) return 0;
  std::string payload;
  if (w->compressor == kZlib) {
    uLongf dst_len = compressBound(w->buf.size());
    payload.resize(dst_len);
    if (compress2(reinterpret_cast<Bytef*>(&payload[0]), &dst_len,
                  reinterpret_cast<const Bytef*>(w->buf.data()),
                  w->buf.size(), Z_DEFAULT_COMPRESSION) != Z_OK)
      return -1;
    payload.resize(dst_len);
  } else {
    payload = w->buf;
  }
  ChunkHeader h;
  h.magic = kMagic;
  h.num_records = w->num_records;
  h.compressor = static_cast<uint32_t>(w->compressor);
  h.crc32 = static_cast<uint32_t>(
      crc32(0L, reinterpret_cast<const Bytef*>(payload.data()), payload.size()));
  h.payload_len = payload.size();
  // raw length follows header so the scanner can size its buffer
  uint64_t raw_len = w->buf.size();
  if (fwrite(&h, sizeof(h), 1, w->f) != 1) return -1;
  if (fwrite(&raw_len, sizeof(raw_len), 1, w->f) != 1) return -1;
  if (!payload.empty() && fwrite(payload.data(), payload.size(), 1, w->f) != 1)
    return -1;
  w->buf.clear();
  w->num_records = 0;
  return 0;
}

void* ptq_recordio_writer_open(const char* path, int compressor,
                               size_t max_chunk_records) {
  FILE* f = fopen(path, "wb");
  if (!f) return nullptr;
  auto* w = new Writer();
  w->f = f;
  w->compressor = compressor;
  w->max_records = max_chunk_records ? max_chunk_records : 1000;
  return w;
}

int ptq_recordio_write(void* wp, const char* data, size_t len) {
  auto* w = static_cast<Writer*>(wp);
  uint32_t l = static_cast<uint32_t>(len);
  w->buf.append(reinterpret_cast<const char*>(&l), sizeof(l));
  w->buf.append(data, len);
  w->num_records++;
  if (w->num_records >= w->max_records) return write_chunk(w);
  return 0;
}

int ptq_recordio_writer_close(void* wp) {
  auto* w = static_cast<Writer*>(wp);
  int rc = write_chunk(w);
  fclose(w->f);
  delete w;
  return rc;
}

struct Scanner {
  FILE* f;
  std::string chunk;          // decompressed current chunk
  size_t offset = 0;
  uint32_t remaining = 0;
};

void* ptq_recordio_scanner_open(const char* path) {
  FILE* f = fopen(path, "rb");
  if (!f) return nullptr;
  auto* s = new Scanner();
  s->f = f;
  return s;
}

static int load_chunk(Scanner* s) {
  ChunkHeader h;
  if (fread(&h, sizeof(h), 1, s->f) != 1) return -1;  // EOF
  if (h.magic != kMagic) return -2;
  uint64_t raw_len;
  if (fread(&raw_len, sizeof(raw_len), 1, s->f) != 1) return -2;
  std::string payload(h.payload_len, '\0');
  if (h.payload_len &&
      fread(&payload[0], h.payload_len, 1, s->f) != 1)
    return -2;
  uint32_t crc = static_cast<uint32_t>(crc32(
      0L, reinterpret_cast<const Bytef*>(payload.data()), payload.size()));
  if (crc != h.crc32) return -3;  // corruption detected
  if (h.compressor == kZlib) {
    s->chunk.resize(raw_len);
    uLongf dst = raw_len;
    if (uncompress(reinterpret_cast<Bytef*>(&s->chunk[0]), &dst,
                   reinterpret_cast<const Bytef*>(payload.data()),
                   payload.size()) != Z_OK)
      return -2;
  } else {
    s->chunk = std::move(payload);
  }
  s->offset = 0;
  s->remaining = h.num_records;
  return 0;
}

// returns record length (malloc'd into *out); -1 EOF; -2 format err; -3 CRC err
long ptq_recordio_next(void* sp, char** out) {
  auto* s = static_cast<Scanner*>(sp);
  if (s->remaining == 0) {
    int rc = load_chunk(s);
    if (rc != 0) return rc;
  }
  uint32_t len;
  memcpy(&len, s->chunk.data() + s->offset, sizeof(len));
  s->offset += sizeof(len);
  *out = static_cast<char*>(malloc(len));
  memcpy(*out, s->chunk.data() + s->offset, len);
  s->offset += len;
  s->remaining--;
  return static_cast<long>(len);
}

void ptq_recordio_scanner_close(void* sp) {
  auto* s = static_cast<Scanner*>(sp);
  fclose(s->f);
  delete s;
}

// ---------------------------------------------------------------------------
// ThreadPool (framework/threadpool.h analogue) — runs C callbacks; the
// Python side uses it through the prefetch pipeline below.
// ---------------------------------------------------------------------------

struct Pool {
  std::vector<std::thread> workers;
  std::deque<std::function<void()>> tasks;
  std::mutex mu;
  std::condition_variable cv;
  bool stop = false;
};

void* ptq_pool_create(int num_threads) {
  auto* p = new Pool();
  for (int i = 0; i < num_threads; ++i) {
    p->workers.emplace_back([p] {
      for (;;) {
        std::function<void()> task;
        {
          std::unique_lock<std::mutex> lk(p->mu);
          p->cv.wait(lk, [p] { return p->stop || !p->tasks.empty(); });
          if (p->stop && p->tasks.empty()) return;
          task = std::move(p->tasks.front());
          p->tasks.pop_front();
        }
        task();
      }
    });
  }
  return p;
}

typedef void (*ptq_task_fn)(void* arg);

void ptq_pool_submit(void* pp, ptq_task_fn fn, void* arg) {
  auto* p = static_cast<Pool*>(pp);
  {
    std::lock_guard<std::mutex> lk(p->mu);
    p->tasks.emplace_back([fn, arg] { fn(arg); });
  }
  p->cv.notify_one();
}

void ptq_pool_destroy(void* pp) {
  auto* p = static_cast<Pool*>(pp);
  {
    std::lock_guard<std::mutex> lk(p->mu);
    p->stop = true;
  }
  p->cv.notify_all();
  for (auto& t : p->workers) t.join();
  delete p;
}


// ---------------------------------------------------------------------------
// Framed-TCP transport (the gRPC byte-transport role for pserver mode:
// reference operators/distributed/grpc_client.h + grpc_server.cc do the
// wire handling in C++, request handlers live above).  Frames are
// u32-length-prefixed byte bodies; partial reads/writes handled here so
// the Python layer above never loops on syscalls.
// ---------------------------------------------------------------------------

// ``tail``, ``verdict`` and ``queued`` belong to the frame pusher below
// (ptq_conn_send_frames); every other entry leaves them alone.
struct Conn {
  int fd;
  std::string tail;   // what the socket would not take without blocking
  int verdict = 0;    // of the frames the pusher has handled: 0, 1 or -1
  size_t queued = 0;  // frames handed to the pusher and not yet handled
};
struct Listener { int fd; };

static int write_all(int fd, const char* p, size_t n) {
  while (n) {
    ssize_t w = ::send(fd, p, n, MSG_NOSIGNAL);
    if (w <= 0) {
      if (w < 0 && (errno == EINTR)) continue;
      return -1;
    }
    p += w;
    n -= static_cast<size_t>(w);
  }
  return 0;
}

static int read_all(int fd, char* p, size_t n) {
  while (n) {
    ssize_t r = ::recv(fd, p, n, 0);
    if (r == 0) return 1;  // eof
    if (r < 0) {
      if (errno == EINTR) continue;
      return -1;
    }
    p += r;
    n -= static_cast<size_t>(r);
  }
  return 0;
}

void* ptq_conn_connect(const char* host, int port, double timeout_s) {
  int fd = ::socket(AF_INET, SOCK_STREAM, 0);
  if (fd < 0) return nullptr;
  sockaddr_in addr{};
  addr.sin_family = AF_INET;
  addr.sin_port = htons(static_cast<uint16_t>(port));
  if (::inet_pton(AF_INET, host, &addr.sin_addr) != 1) {
    ::close(fd);
    return nullptr;
  }
  auto deadline = std::chrono::steady_clock::now() +
                  std::chrono::duration<double>(timeout_s);
  while (::connect(fd, reinterpret_cast<sockaddr*>(&addr),
                   sizeof(addr)) != 0) {
    ::close(fd);
    if (std::chrono::steady_clock::now() >= deadline) return nullptr;
    std::this_thread::sleep_for(std::chrono::milliseconds(100));
    fd = ::socket(AF_INET, SOCK_STREAM, 0);
    if (fd < 0) return nullptr;
  }
  int one = 1;
  ::setsockopt(fd, IPPROTO_TCP, TCP_NODELAY, &one, sizeof(one));
  auto* c = new Conn{fd};
  return c;
}

int ptq_conn_send_frame(void* cp, const char* body, size_t len) {
  auto* c = static_cast<Conn*>(cp);
  uint32_t n = static_cast<uint32_t>(len);
  // one buffer, one write: header+body in a single TCP segment under
  // TCP_NODELAY (two send() calls would emit two packets per frame)
  char* buf = static_cast<char*>(malloc(len + 4));
  if (!buf) return -1;
  memcpy(buf, &n, 4);  // little-endian hosts (x86/ARM TPU VMs)
  memcpy(buf + 4, body, len);
  int rc = write_all(c->fd, buf, len + 4);
  free(buf);
  return rc;
}

// Scatter-gather frame send: the u32 length prefix plus every caller
// buffer goes to the kernel through writev — tensor bytes leave the
// ndarray with NO userspace concat copy (the grpc_serde.cc:35 zero-copy
// ByteBuffer role).  Partial writes advance the iovec in place; iovec
// batches are capped well under IOV_MAX.
int ptq_conn_send_frame_vec(void* cp, void** bufs, const size_t* lens,
                            size_t nbufs) {
  auto* c = static_cast<Conn*>(cp);
  size_t total = 0;
  for (size_t i = 0; i < nbufs; ++i) total += lens[i];
  uint32_t n = static_cast<uint32_t>(total);
  char hdr[4];
  memcpy(hdr, &n, 4);  // little-endian hosts (x86/ARM TPU VMs)

  std::vector<iovec> iov;
  iov.reserve(nbufs + 1);
  iov.push_back({hdr, 4});
  for (size_t i = 0; i < nbufs; ++i) {
    if (lens[i] == 0) continue;
    iov.push_back({bufs[i], lens[i]});
  }
  size_t idx = 0;
  while (idx < iov.size()) {
    size_t cnt = iov.size() - idx;
    if (cnt > 512) cnt = 512;  // stay under IOV_MAX everywhere
    msghdr msg{};
    msg.msg_iov = &iov[idx];
    msg.msg_iovlen = cnt;
    ssize_t w = ::sendmsg(c->fd, &msg, MSG_NOSIGNAL);
    if (w < 0) {
      if (errno == EINTR) continue;
      return -1;
    }
    size_t done = static_cast<size_t>(w);
    while (idx < iov.size() && done >= iov[idx].iov_len) {
      done -= iov[idx].iov_len;
      ++idx;
    }
    if (idx < iov.size() && done) {
      iov[idx].iov_base = static_cast<char*>(iov[idx].iov_base) + done;
      iov[idx].iov_len -= done;
    }
  }
  return 0;
}

// ---------------------------------------------------------------------------
// The frame pusher: one frame to each of n connections in ONE foreign call
// that does no I/O at all — the decode plane's token fan-out
// (decode/engine.py), whose caller is the one thread every stream waits
// for.  The call copies the frames onto a queue; ONE native thread, which
// never needs the interpreter, writes them, each with a send that cannot
// block.  A connection's frames are written in the order they were handed
// in; what a socket will not take at once is remembered on the connection,
// so nothing torn reaches the wire and a slow or dead reader costs the
// others nothing.
// ---------------------------------------------------------------------------

struct PushJob {
  Conn* c;
  std::string frame;  // u32 length + body
};

struct Pusher {
  std::mutex mu;  // guards jobs and every Conn's queued / verdict
  std::condition_variable work, handled;
  std::deque<PushJob> jobs;
};

// set once a frame has been pushed: a process that never pushes starts no
// thread, and closing its connections waits for none
static std::atomic<Pusher*> g_pusher{nullptr};

// -> 0 the whole frame is in the socket, 1 kept on the tail, -1 peer gone
static int push_one(Conn* c, const std::string& frame) {
  size_t sent = 0;
  if (c->tail.empty()) {
    ssize_t w;
    do {
      w = ::send(c->fd, frame.data(), frame.size(),
                 MSG_DONTWAIT | MSG_NOSIGNAL);
    } while (w < 0 && errno == EINTR);
    if (w < 0 && errno != EAGAIN && errno != EWOULDBLOCK) return -1;
    sent = w < 0 ? 0 : static_cast<size_t>(w);
    if (sent == frame.size()) return 0;
  }
  c->tail.append(frame, sent, std::string::npos);
  return 1;
}

static void pusher_loop(Pusher* p) {
  std::deque<PushJob> batch;
  for (;;) {
    {
      std::unique_lock<std::mutex> lk(p->mu);
      p->work.wait(lk, [p] { return !p->jobs.empty(); });
      batch.swap(p->jobs);
    }
    for (auto& job : batch) {
      // ``queued`` > 0 keeps the connection alive and its tail ours
      int verdict = push_one(job.c, job.frame);
      {
        std::lock_guard<std::mutex> lk(p->mu);
        if (verdict != 0 && job.c->verdict == 0) job.c->verdict = verdict;
        --job.c->queued;
      }
      p->handled.notify_all();
    }
    batch.clear();
  }
}

static Pusher* pusher() {
  static Pusher* made = [] {
    auto* p = new Pusher();  // never freed: its thread runs to the end
    std::thread(pusher_loop, p).detach();
    g_pusher.store(p, std::memory_order_release);
    return p;
  }();
  return made;
}

// Wait until the pusher holds no frame of this connection.
static void push_quiesce(Conn* c) {
  Pusher* p = g_pusher.load(std::memory_order_acquire);
  if (!p) return;
  std::unique_lock<std::mutex> lk(p->mu);
  p->handled.wait(lk, [c] { return c->queued == 0; });
}

// ``bodies`` holds the n frame bodies back to back, ``lens`` their lengths.
// rcs[i] says what connection i's EARLIER frames met (the writing happens
// behind the call):
//   0  every one is in its socket, whole; this frame follows them;
//   1  the socket would not take one at once: what was left of it is
//      remembered on the connection and this frame is kept behind it —
//      the caller pushes to this connection no more, and the thread that
//      owns it writes the rest with ptq_conn_finish_frames;
//  -1  the peer is gone (or the handle is null): this frame is dropped, for
//      that connection alone.
void ptq_conn_send_frames(void** conns, const char* bodies,
                          const size_t* lens, size_t n, int* rcs) {
  Pusher* p = pusher();
  const char* body = bodies;
  {
    std::lock_guard<std::mutex> lk(p->mu);
    for (size_t i = 0; i < n; body += lens[i], ++i) {
      auto* c = static_cast<Conn*>(conns[i]);
      rcs[i] = c ? c->verdict : -1;
      if (rcs[i] < 0) continue;
      uint32_t len = static_cast<uint32_t>(lens[i]);
      std::string frame(reinterpret_cast<const char*>(&len), 4);  // LE hosts
      frame.append(body, lens[i]);
      ++c->queued;
      p->jobs.push_back({c, std::move(frame)});
    }
  }
  p->work.notify_one();
}

// Blocking: wait for the pusher to have handled every frame of this
// connection, then write what it had to remember.  The thread that owns the
// connection calls it before it writes anything itself, once the caller of
// ptq_conn_send_frames has been told to push no more: its own frames then
// follow the pushed ones, and the connection is ready to be pushed to again.
int ptq_conn_finish_frames(void* cp) {
  auto* c = static_cast<Conn*>(cp);
  push_quiesce(c);
  if (c->verdict < 0) return -1;
  c->verdict = 0;
  if (c->tail.empty()) return 0;
  int rc = write_all(c->fd, c->tail.data(), c->tail.size());
  c->tail.clear();
  return rc;
}

char* ptq_conn_recv_frame(void* cp, size_t* len_out) {
  auto* c = static_cast<Conn*>(cp);
  char hdr[4];
  int r = read_all(c->fd, hdr, 4);
  if (r != 0) return nullptr;
  uint32_t n;
  memcpy(&n, hdr, 4);
  char* buf = static_cast<char*>(malloc(n ? n : 1));
  if (!buf) return nullptr;
  if (read_all(c->fd, buf, n) != 0) {
    free(buf);
    return nullptr;
  }
  *len_out = n;
  return buf;  // caller frees via ptq_buffer_free
}

void ptq_conn_shutdown(void* cp) {
  // wake a blocked reader WITHOUT freeing: the serving thread owns the
  // handle and closes it when its recv returns EOF
  auto* c = static_cast<Conn*>(cp);
  ::shutdown(c->fd, SHUT_RDWR);
}

void ptq_conn_close(void* cp) {
  auto* c = static_cast<Conn*>(cp);
  ::shutdown(c->fd, SHUT_RDWR);
  push_quiesce(c);  // the pusher's sends fail at once now; none is left
  ::close(c->fd);
  delete c;
}

void* ptq_listener_create(const char* host, int port) {
  int fd = ::socket(AF_INET, SOCK_STREAM, 0);
  if (fd < 0) return nullptr;
  int one = 1;
  ::setsockopt(fd, SOL_SOCKET, SO_REUSEADDR, &one, sizeof(one));
  sockaddr_in addr{};
  addr.sin_family = AF_INET;
  addr.sin_port = htons(static_cast<uint16_t>(port));
  if (::inet_pton(AF_INET, host, &addr.sin_addr) != 1 ||
      ::bind(fd, reinterpret_cast<sockaddr*>(&addr), sizeof(addr)) != 0 ||
      ::listen(fd, 128) != 0) {
    ::close(fd);
    return nullptr;
  }
  return new Listener{fd};
}

int ptq_listener_port(void* lp) {
  auto* l = static_cast<Listener*>(lp);
  sockaddr_in addr{};
  socklen_t alen = sizeof(addr);
  if (::getsockname(l->fd, reinterpret_cast<sockaddr*>(&addr), &alen) != 0)
    return -1;
  return ntohs(addr.sin_port);
}

void ptq_listener_shutdown(void* lp) {
  // wake a blocked accept WITHOUT freeing; the accept loop owns the
  // listener and closes it when accept returns failure
  auto* l = static_cast<Listener*>(lp);
  ::shutdown(l->fd, SHUT_RDWR);
}

void* ptq_listener_accept(void* lp) {
  auto* l = static_cast<Listener*>(lp);
  int fd;
  do {
    fd = ::accept(l->fd, nullptr, nullptr);
  } while (fd < 0 && (errno == EINTR || errno == ECONNABORTED));
  if (fd < 0) return nullptr;
  int one = 1;
  ::setsockopt(fd, IPPROTO_TCP, TCP_NODELAY, &one, sizeof(one));
  return new Conn{fd};
}

void ptq_listener_close(void* lp) {
  auto* l = static_cast<Listener*>(lp);
  ::shutdown(l->fd, SHUT_RDWR);
  ::close(l->fd);
  delete l;
}

}  // extern "C"
