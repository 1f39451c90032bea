// sockreader: one native reader thread for the broker's socket reads.
//
// The thread does the recv(2) calls of every plain-TCP connection and
// the event loop takes what they brought in ONE call a wake-up
// (sr_take: the records and their bytes, copied out under one lock);
// it never touches a Python object or the GIL.  What a read costs the
// busy thread today (a selector callback, the system call, the GIL let
// go and taken back around it) is paid here instead.
//
// Per connection (a "slot"):
//   * the thread works on its OWN dup(2) of the descriptor and closes
//     it itself, in queue order (sr_close queues a marker; the thread
//     takes the descriptor out of its epoll set and closes it): a
//     descriptor number the loop has closed and accept(2) has handed
//     out again is never read;
//   * the slot is armed EPOLLIN | EPOLLONESHOT: it is read once, then
//     nothing more until the loop re-arms it (sr_rearm, after the turn
//     that handled the read; the epoll_ctl is done on this thread), so
//     a connection has at most one read the loop has not handled, and
//     a pause the read asks for takes effect before its next recv;
//   * a new slot joins the epoll set on this thread too, in queue
//     order (sr_open queues it): behind the re-arms the loop asked for
//     before, so a connection opened after another's last bytes came
//     (a client's DISCONNECT, then its reconnect) is not read first;
//     where the epoll entry fails, the slot's one record is the errno;
//   * a paused slot (sr_pause) is not read: its bytes wait in the
//     kernel's socket buffer until sr_resume re-arms it.  The pause
//     waits out a recv of the slot in flight, so no read begins after
//     sr_pause has returned;
//   * a read is one record (slot, offset, length) in arrival order:
//     length 0 is the end of stream, a negative length an errno.  The
//     slot is not armed again after either; the loop closes it.
//
// The loop's eventfd is written when the batch goes from empty to
// non-empty.  The batch's bytes live in one arena of ARENA_CAP bytes
// (a recv asks for at most READ_MAX): while less than READ_MIN of it
// is free and the loop has not taken what it holds, the thread WAITS
// for the loop, loses nothing, and the rest stays in the kernel: the
// reads the thread holds and the loop has not taken are bounded by
// ARENA_CAP bytes, as a selector transport's one read is by its size.
// A record whose slot was closed before the loop took it is dropped
// (a generation a slot), so a reused slot never gets another's bytes.

#include <atomic>
#include <cerrno>
#include <condition_variable>
#include <cstdint>
#include <cstring>
#include <memory>
#include <mutex>
#include <thread>
#include <vector>

#include <fcntl.h>
#include <pthread.h>
#include <sys/epoll.h>
#include <sys/eventfd.h>
#include <sys/socket.h>
#include <time.h>
#include <unistd.h>

namespace {

constexpr int64_t ARENA_CAP = 4ll << 20;
constexpr int64_t READ_MAX = 256ll << 10;  // a selector transport's
constexpr int64_t READ_MIN = 64ll << 10;
constexpr int EVENTS_MAX = 512;

enum : int { FREE = 0, OPEN = 1 };

struct Slot {
    int32_t index = -1;
    // under mu: the descriptor, its state and the pause flag; the
    // thread holds mu across a recv, so a pause waits it out
    std::mutex mu;
    int fd = -1;
    int state = FREE;
    bool paused = false;
    std::atomic<uint32_t> gen{0};      // grows at every close
    std::atomic<bool> closing{false};  // the marker is queued
};

struct Record {
    Slot* slot;
    uint32_t gen;
    int64_t off;
    int64_t len;  // 0: end of stream, < 0: -errno
};

enum : int { OP_REARM = 0, OP_CLOSE = 1, OP_OPEN = 2 };

struct Op {
    Slot* slot;
    int kind;
};

struct Reader {
    std::mutex mu;
    std::condition_variable room;
    // under mu: the batch the loop has not taken, and the arena's
    // bytes [start, used) it holds
    std::vector<Record> records;
    int64_t start = 0, used = 0;
    std::vector<Op> ops;             // under mu: for the thread
    std::vector<Slot*> free_slots;   // under mu
    bool sleeping = false;           // under mu: in epoll_wait(-1)
    bool stopping = false;           // under mu
    std::unique_ptr<char[]> arena;
    // the table grows on the loop thread alone (sr_open); the thread
    // reaches a slot through its epoll entry or its op
    std::vector<Slot*> slots;
    int ep = -1, wake = -1, efd = -1;
    std::thread thread;
    std::atomic<int64_t> recv_ns{0}, recvs{0}, full_waits{0};
};

inline int64_t now_ns() {
    timespec ts;
    clock_gettime(CLOCK_MONOTONIC, &ts);
    return int64_t(ts.tv_sec) * 1000000000ll + ts.tv_nsec;
}

inline void signal(int fd) {
    uint64_t one = 1;
    ssize_t w = ::write(fd, &one, sizeof one);
    (void)w;  // EAGAIN: the counter is already non-zero
}

inline void arm(Reader* r, Slot* sl) {
    epoll_event ev{};
    ev.events = EPOLLIN | EPOLLONESHOT;
    ev.data.ptr = sl;
    ::epoll_ctl(r->ep, EPOLL_CTL_MOD, sl->fd, &ev);
}

// (mu held) one record of the batch; true where it is the batch's first
inline bool push_record(Reader* r, Slot* sl, uint32_t gen, int64_t off,
                        int64_t len) {
    bool first = r->records.empty();
    r->records.push_back(Record{sl, gen, len > 0 ? off : r->used, len});
    if (len > 0) r->used = off + len;
    return first;
}

// (mu held) where the next recv may write, or -1 while stopping;
// waits while the arena is full and the loop holds none of it
int64_t reserve(Reader* r, std::unique_lock<std::mutex>& lk) {
    for (;;) {
        if (r->stopping) return -1;
        if (r->records.empty() && r->start == r->used)
            r->start = r->used = 0;  // all taken: from the top
        if (ARENA_CAP - r->used >= READ_MIN) return r->used;
        r->full_waits.fetch_add(1, std::memory_order_relaxed);
        r->room.wait(lk);
    }
}

void read_slot(Reader* r, Slot* sl) {
    int64_t off;
    {
        std::unique_lock<std::mutex> lk(r->mu);
        off = reserve(r, lk);
    }
    if (off < 0) return;
    int64_t got;
    uint32_t gen;
    {
        std::lock_guard<std::mutex> sk(sl->mu);
        if (sl->state != OPEN || sl->paused) return;  // resume re-arms
        gen = sl->gen.load(std::memory_order_relaxed);
        int64_t want = ARENA_CAP - off;
        if (want > READ_MAX) want = READ_MAX;
        for (;;) {
            int64_t t0 = now_ns();
            ssize_t n = ::recv(sl->fd, r->arena.get() + off, size_t(want),
                               MSG_DONTWAIT);
            int err = errno;
            r->recv_ns.fetch_add(now_ns() - t0, std::memory_order_relaxed);
            r->recvs.fetch_add(1, std::memory_order_relaxed);
            if (n >= 0) {
                got = n;
                break;
            }
            if (err == EINTR) continue;
            if (err == EAGAIN || err == EWOULDBLOCK) {
                arm(r, sl);  // a spurious wake-up: nothing to tell
                return;
            }
            got = -int64_t(err);
            break;
        }
    }
    bool tell;
    {
        std::lock_guard<std::mutex> lk(r->mu);
        tell = push_record(r, sl, gen, off, got);
    }
    if (tell) signal(r->efd);
}

// the open: into the epoll set, armed unless paused meanwhile; a
// failed entry is the slot's errno record (the loop closes it)
void open_slot(Reader* r, Slot* sl) {
    uint32_t gen;
    int err;
    {
        std::lock_guard<std::mutex> sk(sl->mu);
        if (sl->state != OPEN) return;
        // (paused before its first read: in the set, unarmed until
        // sr_resume re-arms it)
        epoll_event ev{};
        ev.events = sl->paused ? EPOLLONESHOT : (EPOLLIN | EPOLLONESHOT);
        ev.data.ptr = sl;
        if (::epoll_ctl(r->ep, EPOLL_CTL_ADD, sl->fd, &ev) == 0) return;
        err = errno;
        gen = sl->gen.load(std::memory_order_relaxed);
    }
    bool tell;
    {
        std::lock_guard<std::mutex> lk(r->mu);
        tell = push_record(r, sl, gen, 0, -int64_t(err));
    }
    if (tell) signal(r->efd);
}

// the close marker: out of the epoll set, the thread's descriptor
// goes, the slot's untaken records go stale, the slot is free
void close_slot(Reader* r, Slot* sl) {
    {
        std::lock_guard<std::mutex> sk(sl->mu);
        ::epoll_ctl(r->ep, EPOLL_CTL_DEL, sl->fd, nullptr);
        ::close(sl->fd);
        sl->fd = -1;
        sl->state = FREE;
        sl->paused = false;
        sl->gen.fetch_add(1, std::memory_order_release);
    }
    std::lock_guard<std::mutex> lk(r->mu);
    sl->closing.store(false, std::memory_order_release);
    r->free_slots.push_back(sl);
}

void run(Reader* r) {
    pthread_setname_np(pthread_self(), "sockreader");
    epoll_event evs[EVENTS_MAX];
    std::vector<Op> ops;
    for (;;) {
        bool idle;
        {
            std::lock_guard<std::mutex> lk(r->mu);
            if (r->stopping) return;
            ops.swap(r->ops);
            idle = ops.empty();
            r->sleeping = idle;
        }
        // (in queue order: a slot's re-arms before its close)
        for (const Op& op : ops) {
            if (op.kind == OP_CLOSE) {
                close_slot(r, op.slot);
                continue;
            }
            if (op.kind == OP_OPEN) {
                open_slot(r, op.slot);
                continue;
            }
            std::lock_guard<std::mutex> sk(op.slot->mu);
            if (op.slot->state == OPEN && !op.slot->paused)
                arm(r, op.slot);
        }
        ops.clear();
        // every event below is of a slot open when the wait began:
        // a close is applied above, before the next wait
        int n = ::epoll_wait(r->ep, evs, EVENTS_MAX, idle ? -1 : 0);
        if (idle) {
            std::lock_guard<std::mutex> lk(r->mu);
            r->sleeping = false;
        }
        for (int i = 0; i < n; i++) {
            Slot* sl = static_cast<Slot*>(evs[i].data.ptr);
            if (sl == nullptr) {
                uint64_t v;
                ssize_t g = ::read(r->wake, &v, sizeof v);
                (void)g;
                continue;
            }
            read_slot(r, sl);
        }
    }
}

// queue ops for the thread, and wake it where it sleeps
void push_ops(Reader* r, const Op* ops, int64_t n) {
    bool wake;
    {
        std::lock_guard<std::mutex> lk(r->mu);
        r->ops.insert(r->ops.end(), ops, ops + n);
        wake = r->sleeping;
        r->sleeping = false;
    }
    if (wake) signal(r->wake);
}

inline Slot* slot_of(Reader* r, int32_t i) {
    return (i >= 0 && size_t(i) < r->slots.size()) ? r->slots[size_t(i)]
                                                    : nullptr;
}

}  // namespace

extern "C" {

void* sr_create() {
    int ep = ::epoll_create1(EPOLL_CLOEXEC);
    int wake = ::eventfd(0, EFD_NONBLOCK | EFD_CLOEXEC);
    int efd = ::eventfd(0, EFD_NONBLOCK | EFD_CLOEXEC);
    Reader* r = nullptr;
    if (ep >= 0 && wake >= 0 && efd >= 0) {
        epoll_event ev{};
        ev.events = EPOLLIN;
        ev.data.ptr = nullptr;
        if (::epoll_ctl(ep, EPOLL_CTL_ADD, wake, &ev) == 0) {
            r = new Reader();
            r->ep = ep;
            r->wake = wake;
            r->efd = efd;
            try {
                r->arena.reset(new char[size_t(ARENA_CAP)]);
                r->thread = std::thread(run, r);
                return r;
            } catch (...) {
                delete r;
            }
        }
    }
    for (int fd : {ep, wake, efd})
        if (fd >= 0) ::close(fd);
    return nullptr;
}

int sr_event_fd(void* h) { return static_cast<Reader*>(h)->efd; }

int64_t sr_arena_cap() { return ARENA_CAP; }

// A slot over the thread's own dup of `fd`, armed by the thread in
// queue order; -1 where the dup fails.
int32_t sr_open(void* h, int fd) {
    Reader* r = static_cast<Reader*>(h);
    int own = ::fcntl(fd, F_DUPFD_CLOEXEC, 0);
    if (own < 0) return -1;
    Slot* sl = nullptr;
    {
        std::lock_guard<std::mutex> lk(r->mu);
        if (!r->free_slots.empty()) {
            sl = r->free_slots.back();
            r->free_slots.pop_back();
        }
    }
    if (sl == nullptr) {
        sl = new Slot();
        sl->index = int32_t(r->slots.size());
        r->slots.push_back(sl);
    }
    {
        std::lock_guard<std::mutex> sk(sl->mu);
        sl->fd = own;
        sl->state = OPEN;
        sl->paused = false;
    }
    Op op{sl, OP_OPEN};
    push_ops(r, &op, 1);
    return sl->index;
}

// Queue-order close: the thread's descriptor goes after what was
// queued before; no record of the slot reaches the loop after it.
void sr_close(void* h, int32_t slot) {
    Reader* r = static_cast<Reader*>(h);
    Slot* sl = slot_of(r, slot);
    if (sl == nullptr || sl->closing.exchange(true)) return;
    Op op{sl, OP_CLOSE};
    push_ops(r, &op, 1);
}

// Read the slots again once: the loop has handled their reads.
void sr_rearm(void* h, int64_t n, const int32_t* slots) {
    Reader* r = static_cast<Reader*>(h);
    std::vector<Op> ops;
    ops.reserve(size_t(n));
    for (int64_t i = 0; i < n; i++) {
        Slot* sl = slot_of(r, slots[i]);
        if (sl != nullptr) ops.push_back(Op{sl, OP_REARM});
    }
    if (!ops.empty()) push_ops(r, ops.data(), int64_t(ops.size()));
}

// No recv of the slot begins after this returns (one in flight is
// waited out); its bytes stay in the kernel until sr_resume.
void sr_pause(void* h, int32_t slot) {
    Slot* sl = slot_of(static_cast<Reader*>(h), slot);
    if (sl == nullptr) return;
    std::lock_guard<std::mutex> sk(sl->mu);
    sl->paused = true;
}

void sr_resume(void* h, int32_t slot) {
    Reader* r = static_cast<Reader*>(h);
    Slot* sl = slot_of(r, slot);
    if (sl == nullptr) return;
    {
        std::lock_guard<std::mutex> sk(sl->mu);
        if (!sl->paused) return;
        sl->paused = false;
    }
    Op op{sl, OP_REARM};
    push_ops(r, &op, 1);
}

// 1 where the slot is open and not paused.
int sr_reading(void* h, int32_t slot) {
    Slot* sl = slot_of(static_cast<Reader*>(h), slot);
    if (sl == nullptr) return 0;
    std::lock_guard<std::mutex> sk(sl->mu);
    return sl->state == OPEN && !sl->paused
        && !sl->closing.load(std::memory_order_acquire);
}

// The batch: up to `cap` records, oldest first, as (slot, offset into
// `blob`, length); a record of a slot closed since is left out.  Their
// bytes are copied into `blob` (sr_arena_cap() bytes), `*nbytes` says
// how many.  Reads the loop's eventfd first, and writes it again where
// records are left behind.  Returns how many records.
int64_t sr_take(void* h, int32_t* slots, int64_t* offs, int64_t* lens,
                int64_t cap, char* blob, int64_t* nbytes) {
    Reader* r = static_cast<Reader*>(h);
    uint64_t v;
    ssize_t g = ::read(r->efd, &v, sizeof v);
    (void)g;
    int64_t n = 0;
    size_t k = 0;
    bool left;
    {
        std::lock_guard<std::mutex> lk(r->mu);
        int64_t base = r->start, end = base;
        for (; k < r->records.size() && n < cap; k++) {
            const Record& rec = r->records[k];
            if (rec.len > 0) end = rec.off + rec.len;
            if (rec.slot->gen.load(std::memory_order_acquire) != rec.gen)
                continue;  // closed since: stale
            slots[n] = rec.slot->index;
            offs[n] = rec.len > 0 ? rec.off - base : 0;
            lens[n] = rec.len;
            n++;
        }
        std::memcpy(blob, r->arena.get() + base, size_t(end - base));
        *nbytes = end - base;
        r->records.erase(r->records.begin(), r->records.begin() + long(k));
        r->start = end;
        left = !r->records.empty();
    }
    r->room.notify_one();
    if (left) signal(r->efd);
    return n;
}

// out[0..4]: ns inside recv(2), recv calls, waits for a full arena,
// records not taken now, slots open now
void sr_stats(void* h, int64_t* out) {
    Reader* r = static_cast<Reader*>(h);
    out[0] = r->recv_ns.load(std::memory_order_relaxed);
    out[1] = r->recvs.load(std::memory_order_relaxed);
    out[2] = r->full_waits.load(std::memory_order_relaxed);
    std::lock_guard<std::mutex> lk(r->mu);
    out[3] = int64_t(r->records.size());
    out[4] = int64_t(r->slots.size()) - int64_t(r->free_slots.size());
}

// Join the thread, close every descriptor it still owns, the epoll
// set and both eventfds (the loop's reader of `efd` the caller removed
// first).
void sr_stop(void* h) {
    Reader* r = static_cast<Reader*>(h);
    {
        std::lock_guard<std::mutex> lk(r->mu);
        r->stopping = true;
    }
    r->room.notify_all();
    signal(r->wake);
    r->thread.join();
    for (Slot* sl : r->slots) {
        if (sl->fd >= 0) ::close(sl->fd);
        delete sl;
    }
    ::close(r->ep);
    ::close(r->wake);
    ::close(r->efd);
    delete r;
}

}  // extern "C"
