// Blocking-queue worker pool — the disk-IO thread analogue.
//
// Reference: storage/storage_dio.c — dedicated reader/writer threads per
// store path pull tasks from blocking queues (dio_thread_entrance), so
// slow file IO never stalls the nio event loops.  Here the storage
// server runs one pool per store path for chunk-store writes,
// fingerprint RPCs, trunk allocation RPCs, and deletes; completions are
// posted back to the owning connection's EventLoop.
#pragma once

#include <algorithm>
#include <chrono>
#include <condition_variable>
#include <deque>
#include <functional>
#include <memory>
#include <mutex>
#include <string>
#include <thread>
#include <vector>

#include "common/net.h"  // MonoUs: the shared latency clock
#include "common/lockrank.h"
#include "common/stats.h"
#include "common/threadreg.h"

namespace fdfs {

// How many workers ONE store path's dio pool gets (storage.conf:
// disk_writer_threads).  A positive `configured` is the operator's pin
// and is taken as it stands (config.cc holds it to kDioWorkersCap).  0
// derives the number from what the daemon can observe: the node's total
// is the host's cores, and the store paths divide it, because the pools
// are per path and what loads the host is their sum.
//
// Why the cores, and not upstream's constant two: a worker here mostly
// waits (a sidecar round trip, a recipe's fsync, a slab's lock), so two
// of them queue every upload behind the two before it.  The curve the
// rule was read from (PERF.md section 6, PR 32; upstream_mix.updown on
// a 13-core v5e host, ten closed-loop callers, MB/s at 2 / 4 / 6 / 8 /
// 10 / 12 / 13 / 16 / 24 workers): 36.8 / 49.1 / 53.2 / 52.5 / 51.0 /
// 49.7 / 53.0 / 48.2 / 54.9.  The knee is at 4-6; from 6 on the callers
// bind, not the pool, and nothing is lost up to 24, so any number on the
// plateau would do THERE.  The cores are the number that also holds on
// another host: what a worker does when it does not wait is CPU work
// (CDC, copies, cpu mode's SHA-1), and more runnable workers than cores
// cannot add any.  backup_node.ingest (four callers) reads 197-213 at
// every point.
//
// kDioWorkersFloor is what shipped before (a path never gets fewer, so
// no host gets a narrower pool than it had, and a count the platform
// does not know, 0, lands there); kDioWorkersCap bounds the threads and
// the memory a pin or a very wide host can ask for (a worker on a
// chunked upload holds one segment: OPERATIONS.md, "Host memory").
constexpr int kDioWorkersFloor = 2;
constexpr int kDioWorkersCap = 64;
inline int DioWorkersPerPath(int configured, unsigned cores, int store_paths) {
  if (configured > 0) return configured;
  const int node_total = std::min(static_cast<int>(cores), kDioWorkersCap);
  return std::max(node_total / std::max(store_paths, 1), kDioWorkersFloor);
}

class WorkerPool {
 public:
  // Workers join the thread ledger as "<name_prefix>/<name_base + i>"
  // ("dio.worker/0", "dio.worker/1", ...); name_base lets a caller with
  // several pools (one per store path) number them in one global
  // sequence.  Empty prefix = unregistered (tools, tests).
  explicit WorkerPool(int threads, const std::string& name_prefix = "",
                      int name_base = 0) {
    if (threads < 1) threads = 1;
    for (int i = 0; i < threads; ++i) {
      std::string name =
          name_prefix.empty()
              ? std::string()
              : name_prefix + "/" + std::to_string(name_base + i);
      threads_.emplace_back([this, name] { Main(name); });
    }
  }

  ~WorkerPool() { Stop(); }

  // Saturation instrumentation (ISSUE 6): every task carries its enqueue
  // timestamp; the dequeue observes queue wait (how long disk work sat
  // behind other disk work — the dio saturation signal) and the return
  // observes service time.  Histograms are registry-owned and shared
  // across pools (their Observe is wait-free); either may be null.
  void SetStats(StatHistogram* queue_wait_us, StatHistogram* service_us) {
    std::lock_guard<RankedMutex> lk(mu_);
    hist_wait_ = queue_wait_us;
    hist_service_ = service_us;
  }

  void Submit(std::function<void()> fn) {
    {
      std::lock_guard<RankedMutex> lk(mu_);
      if (stopping_) return;
      queue_.push_back(Task{std::move(fn), MonoUs()});
    }
    Wake();
  }

  // Drain-then-join: queued tasks still run (a queued chunk write must
  // finish or roll back before the process exits).
  void Stop() {
    {
      std::lock_guard<RankedMutex> lk(mu_);
      if (stopping_) return;
      stopping_ = true;
    }
    Wake();
    for (auto& t : threads_)
      if (t.joinable()) t.join();
    threads_.clear();
  }

  size_t pending() const {
    std::lock_guard<RankedMutex> lk(mu_);
    return queue_.size();
  }

 private:
  struct Task {
    std::function<void()> fn;
    int64_t enqueue_us = 0;
  };

  void Main(const std::string& ledger_name) {
    // Optional because tools construct throwaway pools; the destructor
    // must run before the thread exits, hence the stack scope here.
    std::unique_ptr<ScopedThreadName> reg;
    if (!ledger_name.empty())
      reg = std::make_unique<ScopedThreadName>(ledger_name);
    for (;;) {
      Task task;
      StatHistogram* hw = nullptr;
      StatHistogram* hs = nullptr;
      bool have = false;
      // Snapshot the wake generation BEFORE checking the queue: a
      // Submit that lands after the snapshot bumps it, so the idle
      // wait below returns immediately instead of missing the wakeup.
      uint64_t gen;
      {
        std::lock_guard<std::mutex> wl(wake_->mu);  // NOLINT(lock-raw-mutex)
        gen = wake_->gen;
      }
      {
        std::lock_guard<RankedMutex> lk(mu_);
        if (!queue_.empty()) {
          task = std::move(queue_.front());
          queue_.pop_front();
          hw = hist_wait_;
          hs = hist_service_;
          have = true;
        } else if (stopping_) {
          return;  // stopping and drained
        }
      }
      // One beat per dequeue or idle round (~1/s): an idle worker keeps
      // beating its watchdog heartbeat, while a worker wedged INSIDE
      // task.fn() (stuck fsync) stops beating and gets flagged.
      BeatThreadHeartbeat();
      if (!have) {
        // The idle wait lives on its own plain mutex, never nested
        // with mu_: condition_variable_any's timed wait re-locks the
        // outer (ranked) mutex while still holding its internal one —
        // a real lock-order inversion TSan rightly flags.  The deadline
        // is system_clock on purpose: a steady-clock wait_for lowers to
        // pthread_cond_clockwait, which older libtsan does not
        // intercept (phantom double-lock/race reports); the wall-clock
        // worst case is one early or late heartbeat slice, nothing
        // correctness-bearing.
        std::unique_lock<std::mutex> wl(wake_->mu);  // NOLINT(lock-raw-mutex)
        wake_->cv.wait_until(wl,
                             std::chrono::system_clock::now() +
                                 std::chrono::seconds(1),
                             [this, gen] { return wake_->gen != gen; });
        continue;
      }
      int64_t t0 = MonoUs();
      if (hw != nullptr) hw->Observe(t0 - task.enqueue_us);
      task.fn();
      if (hs != nullptr) hs->Observe(MonoUs() - t0);
    }
  }

  void Wake() {
    {
      std::lock_guard<std::mutex> wl(wake_->mu);  // NOLINT(lock-raw-mutex)
      ++wake_->gen;
    }
    wake_->cv.notify_all();
  }

  mutable RankedMutex mu_{LockRank::kWorkers};
  std::deque<Task> queue_;
  std::vector<std::thread> threads_;
  bool stopping_ = false;
  StatHistogram* hist_wait_ = nullptr;     // guarded by mu_ (read at dequeue)
  StatHistogram* hist_service_ = nullptr;
  // Wakeup channel, deliberately OUTSIDE the ranked-lock world: taken
  // alone by both sides (Submit/Stop after releasing mu_, workers
  // before taking mu_), so no ordering with mu_ exists at all.
  // Heap-allocated: a stack-resident sync object can inherit a dead
  // prior frame's TSan metadata (atomics have no destroy hook), while
  // freed heap ranges are always scrubbed.
  struct WakeChannel {
    std::mutex mu;               // NOLINT(lock-raw-mutex): rankless by design
    std::condition_variable cv;  // NOLINT(lock-raw-mutex): pairs with mu
    uint64_t gen = 0;            // guarded by mu
  };
  std::unique_ptr<WakeChannel> wake_ = std::make_unique<WakeChannel>();
};

}  // namespace fdfs
