// sockwriter: one native sender thread for the broker's socket writes.
//
// The event loop hands a flush scope's writes over in ONE call
// (sw_submit: the bytes are copied into a batch, the thread is woken
// once); the thread does the send(2) calls with MSG_DONTWAIT, strictly
// first-in first-out, and never touches a Python object or the GIL.
// What the system call costs (the kernel's loopback delivery, the
// wake-up of the peer) is paid here instead of on the loop thread.
//
// Per connection (a "slot"):
//   * the thread works on its OWN dup(2) of the descriptor and closes
//     it itself, in queue order (sw_close queues a marker): a
//     descriptor number the loop has closed and accept(2) has handed
//     out again is never written to;
//   * `pending` counts the bytes handed over and not yet sent, taken
//     back or dropped: the loop writes to its transport only while it
//     reads 0, so the wire carries one order across both paths;
//   * EAGAIN or a short send PARKS the slot: the remainder and every
//     later entry of the slot collect in `parked`, in order, the loop
//     is told through the eventfd and takes them back (sw_take) for
//     its transport, which owns the connection until its buffer is
//     empty (sw_unpark);
//   * any other errno FAILS the slot: what is left is dropped and the
//     loop is told the errno, to close the connection.
//
// The queue is bounded: sw_submit waits (GIL released by ctypes) while
// more than QUEUE_CAP bytes are queued, which is where a loop that
// outruns the thread is held back, as send(2) itself held it before.

#include <atomic>
#include <cerrno>
#include <condition_variable>
#include <cstdint>
#include <cstring>
#include <deque>
#include <memory>
#include <mutex>
#include <string>
#include <thread>
#include <vector>

#include <fcntl.h>
#include <pthread.h>
#include <sys/eventfd.h>
#include <sys/socket.h>
#include <time.h>
#include <unistd.h>

namespace {

enum : int { FREE = 0, OPEN = 1, PARKED = 2, FAILED = 3 };

constexpr int64_t QUEUE_CAP = 64ll << 20;

struct Slot {
    int32_t index = -1;
    int fd = -1;
    std::atomic<int> state{FREE};
    std::atomic<int64_t> pending{0};
    // under Sender::mu
    std::string parked;
    int err = 0;
    bool flagged = false;
    // the close marker is queued: nothing more is accepted
    std::atomic<bool> closing{false};
};

struct Entry {
    Slot* slot;
    int64_t off;
    int64_t len;  // < 0: the close marker
};

struct Batch {
    std::vector<Entry> entries;
    std::unique_ptr<char[]> buf;
    int64_t bytes = 0;
};

struct Sender {
    std::mutex mu;
    std::condition_variable work, room;
    std::deque<Batch*> queue;    // under mu
    int64_t queued_bytes = 0;    // under mu
    bool stopping = false;       // under mu
    std::vector<Slot*> free_slots;   // under mu
    std::vector<Slot*> events;       // under mu
    // the table grows on the loop thread alone (sw_open); the thread
    // reaches a slot through the pointer its entry carries
    std::vector<Slot*> slots;
    int efd = -1;
    std::thread thread;
    std::atomic<int64_t> send_ns{0}, sends{0}, parks{0};
};

inline int64_t now_ns() {
    timespec ts;
    clock_gettime(CLOCK_MONOTONIC, &ts);
    return int64_t(ts.tv_sec) * 1000000000ll + ts.tv_nsec;
}

// (mu held) tell the loop about this slot, once until it polls
inline void flag(Sender* s, Slot* sl) {
    if (!sl->flagged) {
        sl->flagged = true;
        s->events.push_back(sl);
    }
}

// one data entry; returns true where the loop has to be told
bool send_entry(Sender* s, Slot* sl, const char* p, int64_t left) {
    int st = sl->state.load(std::memory_order_acquire);
    if (st == PARKED) {
        std::lock_guard<std::mutex> lk(s->mu);
        sl->parked.append(p, size_t(left));
        flag(s, sl);
        return true;
    }
    if (st != OPEN) {  // failed, or closed under a stale entry
        sl->pending.fetch_sub(left, std::memory_order_release);
        return false;
    }
    while (left > 0) {
        int64_t t0 = now_ns();
        ssize_t r = ::send(sl->fd, p, size_t(left),
                           MSG_DONTWAIT | MSG_NOSIGNAL);
        int err = errno;
        s->send_ns.fetch_add(now_ns() - t0, std::memory_order_relaxed);
        s->sends.fetch_add(1, std::memory_order_relaxed);
        if (r == left) {
            sl->pending.fetch_sub(left, std::memory_order_release);
            return false;
        }
        if (r < 0 && err == EINTR) continue;
        bool full = r >= 0 || err == EAGAIN || err == EWOULDBLOCK
            || err == ENOBUFS || err == ENOMEM;
        if (r > 0) {
            p += r;
            left -= r;
            sl->pending.fetch_sub(r, std::memory_order_release);
        }
        std::lock_guard<std::mutex> lk(s->mu);
        if (full) {
            sl->parked.append(p, size_t(left));
            sl->state.store(PARKED, std::memory_order_release);
            s->parks.fetch_add(1, std::memory_order_relaxed);
        } else {
            sl->err = err;
            sl->state.store(FAILED, std::memory_order_release);
            sl->pending.fetch_sub(left, std::memory_order_release);
        }
        flag(s, sl);
        return true;
    }
    return false;
}

// the close marker: the thread's own descriptor goes, in queue order
void close_slot(Sender* s, Slot* sl) {
    ::close(sl->fd);
    std::lock_guard<std::mutex> lk(s->mu);
    sl->fd = -1;
    sl->parked.clear();
    sl->parked.shrink_to_fit();
    sl->err = 0;
    sl->closing.store(false, std::memory_order_release);
    sl->pending.store(0, std::memory_order_release);
    sl->state.store(FREE, std::memory_order_release);
    s->free_slots.push_back(sl);
}

void run(Sender* s) {
    pthread_setname_np(pthread_self(), "sockwriter");
    for (;;) {
        std::deque<Batch*> take;
        {
            std::unique_lock<std::mutex> lk(s->mu);
            s->work.wait(lk, [s] {
                return !s->queue.empty() || s->stopping;
            });
            if (s->queue.empty()) return;  // stopping, and drained
            take.swap(s->queue);
        }
        for (Batch* b : take) {
            bool tell = false;
            for (const Entry& e : b->entries) {
                if (e.len < 0)
                    close_slot(s, e.slot);
                else if (send_entry(s, e.slot, b->buf.get() + e.off, e.len))
                    tell = true;
            }
            {
                std::lock_guard<std::mutex> lk(s->mu);
                s->queued_bytes -= b->bytes;
            }
            s->room.notify_all();
            delete b;
            if (tell) {
                uint64_t one = 1;
                ssize_t w = ::write(s->efd, &one, sizeof one);
                (void)w;  // EAGAIN: the counter is already non-zero
            }
        }
    }
}

// (mu held) queue a batch and wake the thread
void push(Sender* s, Batch* b) {
    s->queued_bytes += b->bytes;
    s->queue.push_back(b);
}

inline Slot* slot_of(Sender* s, int32_t i) {
    return (i >= 0 && size_t(i) < s->slots.size()) ? s->slots[size_t(i)]
                                                    : nullptr;
}

}  // namespace

extern "C" {

void* sw_create() {
    int efd = ::eventfd(0, EFD_NONBLOCK | EFD_CLOEXEC);
    if (efd < 0) return nullptr;
    Sender* s = new Sender();
    s->efd = efd;
    try {
        s->thread = std::thread(run, s);
    } catch (...) {
        ::close(efd);
        delete s;
        return nullptr;
    }
    return s;
}

int sw_event_fd(void* h) { return static_cast<Sender*>(h)->efd; }

// A slot over the thread's own dup of `fd`; -1 where dup fails.
int32_t sw_open(void* h, int fd) {
    Sender* s = static_cast<Sender*>(h);
    int own = ::fcntl(fd, F_DUPFD_CLOEXEC, 0);
    if (own < 0) return -1;
    Slot* sl = nullptr;
    {
        std::lock_guard<std::mutex> lk(s->mu);
        if (!s->free_slots.empty()) {
            sl = s->free_slots.back();
            s->free_slots.pop_back();
        }
    }
    if (sl == nullptr) {
        sl = new Slot();
        sl->index = int32_t(s->slots.size());
        s->slots.push_back(sl);
    }
    sl->fd = own;
    sl->pending.store(0, std::memory_order_relaxed);
    sl->state.store(OPEN, std::memory_order_release);
    return sl->index;
}

// Queue-order close: what was handed over before goes out first (a
// parked slot's bytes are dropped), then the thread's descriptor.
void sw_close(void* h, int32_t slot) {
    Sender* s = static_cast<Sender*>(h);
    Slot* sl = slot_of(s, slot);
    if (sl == nullptr || sl->state.load(std::memory_order_acquire) == FREE)
        return;
    Batch* b = new Batch();
    b->entries.push_back(Entry{sl, 0, -1});
    {
        std::lock_guard<std::mutex> lk(s->mu);
        if (sl->closing.exchange(true)) {  // one marker a slot
            delete b;
            return;
        }
        push(s, b);
    }
    s->work.notify_one();
}

// One flush scope: n writes, copied, queued, one wake-up.  Returns
// the bytes queued.
int64_t sw_submit(void* h, int64_t n, const int32_t* slots,
                  const char* const* datas, const int64_t* lens) {
    Sender* s = static_cast<Sender*>(h);
    int64_t total = 0;
    for (int64_t i = 0; i < n; i++) total += lens[i] > 0 ? lens[i] : 0;
    Batch* b = new Batch();
    b->entries.reserve(size_t(n));
    b->buf.reset(new char[size_t(total > 0 ? total : 1)]);
    int64_t off = 0;
    for (int64_t i = 0; i < n; i++) {
        Slot* sl = slot_of(s, slots[i]);
        // (an entry behind its slot's close marker would be sent by
        // the slot's next owner)
        if (sl == nullptr || lens[i] <= 0
            || sl->closing.load(std::memory_order_acquire)
            || sl->state.load(std::memory_order_acquire) == FREE)
            continue;
        std::memcpy(b->buf.get() + off, datas[i], size_t(lens[i]));
        sl->pending.fetch_add(lens[i], std::memory_order_release);
        b->entries.push_back(Entry{sl, off, lens[i]});
        off += lens[i];
    }
    b->bytes = off;
    if (b->entries.empty()) {
        delete b;
        return 0;
    }
    {
        std::unique_lock<std::mutex> lk(s->mu);
        s->room.wait(lk, [s] {
            return s->queued_bytes <= QUEUE_CAP || s->stopping;
        });
        push(s, b);
    }
    s->work.notify_one();
    return off;
}

int64_t sw_pending(void* h, int32_t slot) {
    Slot* sl = slot_of(static_cast<Sender*>(h), slot);
    return sl ? sl->pending.load(std::memory_order_acquire) : 0;
}

// What the thread has to tell: up to `cap` slots that are parked with
// bytes to take back (errs 0, lens the bytes) or failed (errs the
// errno).  Returns how many; the eventfd is the caller's to read.
int64_t sw_poll(void* h, int32_t* slots, int32_t* errs, int64_t* lens,
                int64_t cap) {
    Sender* s = static_cast<Sender*>(h);
    std::lock_guard<std::mutex> lk(s->mu);
    int64_t n = 0;
    size_t i = 0;
    for (; i < s->events.size() && n < cap; i++) {
        Slot* sl = s->events[i];
        sl->flagged = false;
        int st = sl->state.load(std::memory_order_acquire);
        if (st == PARKED && !sl->parked.empty()) {
            slots[n] = sl->index;
            errs[n] = 0;
            lens[n] = int64_t(sl->parked.size());
            n++;
        } else if (st == FAILED && sl->err != 0) {
            slots[n] = sl->index;
            errs[n] = sl->err;
            lens[n] = 0;
            sl->err = 0;  // told once
            n++;
        }
    }
    s->events.erase(s->events.begin(), s->events.begin() + long(i));
    if (!s->events.empty()) {
        uint64_t one = 1;
        ssize_t w = ::write(s->efd, &one, sizeof one);
        (void)w;
    }
    return n;
}

// Take a parked slot's bytes back, oldest first, in one locked call.
int64_t sw_take(void* h, int32_t slot, char* out, int64_t cap) {
    Sender* s = static_cast<Sender*>(h);
    Slot* sl = slot_of(s, slot);
    if (sl == nullptr) return 0;
    std::lock_guard<std::mutex> lk(s->mu);
    int64_t n = int64_t(sl->parked.size());
    if (n > cap) n = cap;
    if (n <= 0) return 0;
    std::memcpy(out, sl->parked.data(), size_t(n));
    sl->parked.erase(0, size_t(n));
    sl->pending.fetch_sub(n, std::memory_order_release);
    return n;
}

// The transport's buffer is empty again and the thread holds nothing
// of the slot: it takes the slot's writes once more.
void sw_unpark(void* h, int32_t slot) {
    Sender* s = static_cast<Sender*>(h);
    Slot* sl = slot_of(s, slot);
    if (sl == nullptr) return;
    std::lock_guard<std::mutex> lk(s->mu);
    if (sl->state.load(std::memory_order_acquire) == PARKED
        && sl->parked.empty()
        && sl->pending.load(std::memory_order_acquire) == 0)
        sl->state.store(OPEN, std::memory_order_release);
}

// out[0..4]: ns inside send(2), send calls, parks, bytes queued now,
// slots open now
void sw_stats(void* h, int64_t* out) {
    Sender* s = static_cast<Sender*>(h);
    out[0] = s->send_ns.load(std::memory_order_relaxed);
    out[1] = s->sends.load(std::memory_order_relaxed);
    out[2] = s->parks.load(std::memory_order_relaxed);
    std::lock_guard<std::mutex> lk(s->mu);
    out[3] = s->queued_bytes;
    out[4] = int64_t(s->slots.size()) - int64_t(s->free_slots.size());
}

// Drain the queue, join the thread, close every descriptor it still
// owns and the eventfd (whose reader the caller removed first).
void sw_stop(void* h) {
    Sender* s = static_cast<Sender*>(h);
    {
        std::lock_guard<std::mutex> lk(s->mu);
        s->stopping = true;
    }
    s->work.notify_all();
    s->room.notify_all();
    s->thread.join();
    for (Slot* sl : s->slots) {
        if (sl->fd >= 0) ::close(sl->fd);
        delete sl;
    }
    ::close(s->efd);
    delete s;
}

}  // extern "C"
