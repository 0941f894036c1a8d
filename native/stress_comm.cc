// Concurrency stress harness for the native communicator, built to run
// under TSan and ASan/UBSan in CI (make tsan / make asan).
//
// Everything runs IN ONE PROCESS (sanitizers cannot see across fork):
// an in-process StoreServer, one Communicator per rank, one op thread per
// rank (the native contract: ops are serialized per communicator), and a
// controller thread that injects the exact overlap production hits —
// abort() fired mid-collective from a foreign thread, then configure()
// called while the superseded op thread is still unwinding (the
// torn-EpochIO-pointer class the PR 8 review caught; the atomic
// epoch-scalar members and the LanePool submit-after-stop inline path
// exist because THIS harness flagged them).
//
// Phases:
//   A  correctness churn — allreduce / reduce_scatter / alltoall /
//      allgather / broadcast / p2p, every result verified bit-exactly,
//      all ranks concurrent (exercises LanePool, the striped send/recv
//      paths, and OpLatch under real thread interleavings);
//   B  abort + epoch-swap churn — op threads hammer verified allreduces
//      nonstop while the controller repeatedly aborts every communicator
//      mid-flight and drives a full re-rendezvous; op-thread errors are
//      expected and swallowed, every SUCCESSFUL op must still verify
//      (a torn epoch that silently corrupts data fails here), and each
//      settled epoch must complete at least one verified allreduce per
//      rank.
//
//   P  (``stress_comm_* pieces [count] [MiB]``, in place of A and B) — a
//      step of ``ddp.allreduce_pytree`` on a leaf over the bucket cap: two
//      ranks, ONE bfloat16 buffer a rank, ``count`` pieces of it rung one
//      after another in place through ``allreduce_iov`` with the divisor,
//      while a second thread a rank (the train thread's pack) writes the
//      pieces after the one in the ring into the same buffer; every piece
//      verified.  89 pieces of 16 MiB are the four-chip cell's step.
//      Runs at what ``auto`` resolves to with no link emulated
//      (kUnshapedAutoLanes, PR 47): the count a deployment's rings run at.
//      Rank 0 rings its pieces through ONE ring session (``run_session``:
//      the pack thread pushes each piece as it has written it, a third
//      thread waits for "piece k is rung" and verifies it at once), rank 1
//      piece by piece through ``allreduce_iov``: the two ride the same rings.
//
//   C  ring sessions under abort + epoch-swap churn (after B) — every rank's
//      op thread inside ``run_session`` while a pusher hands pieces over
//      with naps between them and a waiter waits for each; the controller
//      aborts every communicator mid-session (some ranks wait for a push,
//      some are mid-ring), every run and every wait must RETURN, a piece
//      that reports rung must verify, and after a re-rendezvous a clean
//      session of the same shape must ring every piece.
//
// Phases A and B run at TORCHFT_RING_LANES=2 so the per-lane worker pool
// and the lane-striped framing are engaged throughout; abort
// mid-striped-op is the native tier's lane-failover story (every lane to
// the peer dies at once).
//
// Exit 0 on success.  Sanitizer findings fail the run via halt_on_error
// (CI sets TSAN_OPTIONS / ASAN_OPTIONS / UBSAN_OPTIONS).

#include <atomic>
#include <chrono>
#include <cstdio>
#include <cstdlib>
#include <cstring>
#include <string>
#include <thread>
#include <vector>

#include "comm.h"
#include "store.h"

#if defined(__SANITIZE_THREAD__)
#include <pthread.h>
#include <time.h>
// This toolchain's libtsan intercepts pthread_cond_timedwait but NOT
// pthread_cond_clockwait (added in glibc 2.30; libstdc++'s
// condition_variable::wait_until uses it for steady_clock deadlines).  An
// unintercepted clockwait means TSan never sees the mutex release inside
// the wait, corrupting its lock bookkeeping into flaky bogus
// "double lock of a mutex" reports at the next honest lock site
// (reproduced ~1/5 runs against store.h's STORE_GET wait).  Interpose the
// missing symbol and forward to the intercepted timedwait with the
// deadline rebased onto the condvar's clock (CLOCK_REALTIME for a
// default-initialized pthread_cond) — semantics preserved modulo realtime
// jumps during a test wait, and tsan.supp stays empty.
extern "C" int pthread_cond_clockwait(pthread_cond_t* cond,
                                      pthread_mutex_t* mu, clockid_t clock,
                                      const struct timespec* abstime) {
  struct timespec now_clock, now_real, real_abs;
  ::clock_gettime(clock, &now_clock);
  ::clock_gettime(CLOCK_REALTIME, &now_real);
  int64_t rem_ns = (abstime->tv_sec - now_clock.tv_sec) * 1000000000LL +
                   (abstime->tv_nsec - now_clock.tv_nsec);
  if (rem_ns < 0) rem_ns = 0;
  int64_t real_ns =
      now_real.tv_sec * 1000000000LL + now_real.tv_nsec + rem_ns;
  real_abs.tv_sec = real_ns / 1000000000LL;
  real_abs.tv_nsec = real_ns % 1000000000LL;
  return ::pthread_cond_timedwait(cond, mu, &real_abs);
}
#endif

using namespace tpuft;

namespace {

constexpr int kWorld = 3;
constexpr size_t kReduceFloats = 256 << 10;  // 1 MiB: engages 2 lanes
constexpr size_t kChunkBytes = 64 << 10;
constexpr int kPhaseAIters = 4;
constexpr int kPhaseBEpochs = 5;
constexpr double kOpTimeoutS = 20.0;

std::atomic<int> g_failures{0};

void fail(const std::string& msg) {
  std::fprintf(stderr, "FAIL: %s\n", msg.c_str());
  g_failures.fetch_add(1);
}

void check(bool ok, const std::string& msg) {
  if (!ok) fail(msg);
}

double expected_sum(int world) {
  double s = 0;
  for (int r = 0; r < world; ++r) s += r + 1;
  return s;
}

// ---------------------------------------------------------------------------
// Phase A: verified collective churn, stable epoch
// ---------------------------------------------------------------------------

void phase_a_rank(Communicator* comm, int rank, const std::string& store_addr) {
  comm->configure(store_addr + "/stress_a", rank, kWorld);
  std::vector<float> buf(kReduceFloats);
  std::vector<uint8_t> bytes_in(kChunkBytes * kWorld), bytes_out(kChunkBytes * kWorld);
  const float want_sum = static_cast<float>(expected_sum(kWorld));

  for (int it = 0; it < kPhaseAIters; ++it) {
    // allreduce
    std::fill(buf.begin(), buf.end(), static_cast<float>(rank + 1));
    comm->allreduce(buf.data(), buf.size() * 4, DT_F32, OP_SUM);
    for (size_t i = 0; i < buf.size(); ++i) {
      if (buf[i] != want_sum) {
        fail("phase A allreduce corrupt at " + std::to_string(i));
        break;
      }
    }

    // allreduce with a divisor: the owner of a chunk divides it in the last
    // reduce step's add, the allgather carries averages (send workers reading
    // what the rx lanes have just written); float32 and, over two scattered
    // segments, bfloat16 (3 is no power of two: a reciprocal would round
    // otherwise)
    std::fill(buf.begin(), buf.end(), static_cast<float>(rank + 1));
    comm->allreduce(buf.data(), buf.size() * 4, DT_F32, OP_SUM, /*divisor=*/3);
    for (size_t i = 0; i < buf.size(); ++i) {
      if (buf[i] != want_sum / 3.0f) {
        fail("phase A averaging allreduce corrupt at " + std::to_string(i));
        break;
      }
    }
    std::vector<uint16_t> half_a(kReduceFloats / 2 + 1, f32_to_bf16(rank + 1.0f));
    std::vector<uint16_t> half_b(kReduceFloats / 3, f32_to_bf16(rank + 1.0f));
    void* segs[2] = {half_a.data(), half_b.data()};
    uint64_t lens[2] = {half_a.size() * 2, half_b.size() * 2};
    comm->allreduce_iov(segs, lens, 2, DT_BF16, OP_SUM, /*divisor=*/3,
                        /*group=*/1);
    const uint16_t want_avg = f32_to_bf16(bf16_to_f32(f32_to_bf16(want_sum)) / 3.0f);
    for (const auto* half : {&half_a, &half_b})
      for (size_t i = 0; i < half->size(); ++i) {
        if ((*half)[i] != want_avg) {
          fail("phase A averaging bf16 allreduce corrupt at " + std::to_string(i));
          break;
        }
      }

    // reduce_scatter: own chunk fully reduced
    std::fill(buf.begin(), buf.end(), static_cast<float>(rank + 1));
    std::vector<float> own(buf.size() / kWorld + kWorld);
    size_t got = comm->reduce_scatter(buf.data(), buf.size() * 4, DT_F32,
                                      OP_SUM, own.data(), own.size() * 4);
    for (size_t i = 0; i < got / 4; ++i) {
      if (own[i] != want_sum) {
        fail("phase A reduce_scatter corrupt at " + std::to_string(i));
        break;
      }
    }

    // alltoall: chunk for peer p carries byte (rank*16 + p)
    for (int p = 0; p < kWorld; ++p)
      std::memset(bytes_in.data() + p * kChunkBytes,
                  rank * 16 + p, kChunkBytes);
    comm->alltoall(bytes_in.data(), bytes_out.data(), kChunkBytes,
                   /*tag=*/7 + it);
    for (int p = 0; p < kWorld; ++p) {
      uint8_t want = static_cast<uint8_t>(p * 16 + rank);
      if (bytes_out[p * kChunkBytes] != want ||
          bytes_out[(p + 1) * kChunkBytes - 1] != want)
        fail("phase A alltoall corrupt from rank " + std::to_string(p));
    }

    // allgather
    std::memset(bytes_in.data(), 100 + rank, kChunkBytes);
    comm->allgather(bytes_in.data(), bytes_out.data(), kChunkBytes,
                    /*tag=*/3 + it);
    for (int p = 0; p < kWorld; ++p)
      if (bytes_out[p * kChunkBytes] != 100 + p)
        fail("phase A allgather corrupt from rank " + std::to_string(p));

    // broadcast (rotating root) — 1 MiB payload so it stripes
    int root = it % kWorld;
    std::fill(buf.begin(), buf.end(),
              rank == root ? static_cast<float>(42 + it) : 0.0f);
    comm->broadcast(buf.data(), buf.size() * 4, root);
    if (buf.front() != static_cast<float>(42 + it) ||
        buf.back() != static_cast<float>(42 + it))
      fail("phase A broadcast corrupt");

    // p2p ring: rank -> rank+1
    uint8_t token[64];
    std::memset(token, rank + 1, sizeof(token));
    int right = (rank + 1) % kWorld;
    int left = (rank + kWorld - 1) % kWorld;
    if (rank % 2 == 0) {
      comm->send(token, sizeof(token), right, /*tag=*/5);
      auto rx = comm->recv_dynamic(left, /*tag=*/5);
      check(rx.size() == sizeof(token) && rx[0] == uint8_t(left + 1),
            "phase A p2p corrupt (even)");
    } else {
      auto rx = comm->recv_dynamic(left, /*tag=*/5);
      comm->send(token, sizeof(token), right, /*tag=*/5);
      check(rx.size() == sizeof(token) && rx[0] == uint8_t(left + 1),
            "phase A p2p corrupt (odd)");
    }
  }
}

// ---------------------------------------------------------------------------
// Phase B: abort + epoch-swap churn against in-flight ops
// ---------------------------------------------------------------------------

struct BState {
  std::atomic<int> epoch{0};         // controller bumps after each reconfigure
  std::atomic<bool> stop{false};
  std::atomic<uint64_t> settled{0};  // bitmask: rank r verified epoch bit
};

// Op thread: hammers verified allreduces NONSTOP, never configures.  The
// controller aborts and re-rendezvouses this communicator from foreign
// threads while we are mid-op — the exact overlap CppCommunicator
// produces (tpuft_comm_configure runs on the caller thread while the
// superseded epoch's op thread is still unwinding).  Errors are expected
// churn; any op that REPORTS success must still be bit-exact.
void phase_b_rank(Communicator* comm, int rank, BState* st) {
  std::vector<float> buf(kReduceFloats);
  const float want_sum = static_cast<float>(expected_sum(kWorld));

  while (!st->stop.load()) {
    std::fill(buf.begin(), buf.end(), static_cast<float>(rank + 1));
    int epoch_at_start = st->epoch.load();
    try {
      comm->allreduce(buf.data(), buf.size() * 4, DT_F32, OP_SUM);
    } catch (const std::exception&) {
      // aborted / superseded / mid-rendezvous: expected under churn; the
      // brief nap keeps the error path from spinning hot while the
      // controller rebuilds the epoch
      std::this_thread::sleep_for(std::chrono::microseconds(500));
      continue;
    }
    bool ok = true;
    for (size_t i = 0; i < buf.size(); ++i) {
      if (buf[i] != want_sum) {
        ok = false;
        fail("phase B silent corruption at index " + std::to_string(i));
        break;
      }
    }
    // only ops that ran wholly inside one controller epoch count toward
    // settling it (the controller zeroes the mask after publishing the
    // epoch, so a re-verify next iteration restores any cleared bit)
    if (ok && st->epoch.load() == epoch_at_start)
      st->settled.fetch_or(uint64_t(1) << rank);
  }
}

// ---------------------------------------------------------------------------
// Phase P: a leaf's pieces rung in place while the next ones are written
// ---------------------------------------------------------------------------

void phase_p_rank(Communicator* comm, int rank, const std::string& store_addr,
                  size_t count, size_t piece_elems) {
  comm->configure(store_addr + "/stress_p", rank, 2);
  std::vector<uint16_t> leaf(count * piece_elems);
  std::atomic<size_t> packed{0};  // pieces written and handed to the ring
  // the train thread's pack: piece k holds rank + 1 + k % 5 in every element
  std::thread pack([&] {
    for (size_t k = 0; k < count; ++k) {
      std::fill(leaf.begin() + k * piece_elems,
                leaf.begin() + (k + 1) * piece_elems,
                f32_to_bf16(static_cast<float>(rank + 1 + k % 5)));
      packed.store(k + 1, std::memory_order_release);
    }
  });
  // (1 + k%5) + (2 + k%5) = 3 + 2 (k%5), halved: x.5 is a bfloat16
  auto verify = [&](size_t k) {
    const uint16_t want = f32_to_bf16(1.5f + static_cast<float>(k % 5));
    const uint16_t* piece = leaf.data() + k * piece_elems;
    for (size_t i = 0; i < piece_elems; ++i) {
      if (piece[i] != want) {
        fail("phase P piece " + std::to_string(k) + " corrupt at " +
             std::to_string(i));
        break;
      }
    }
  };
  if (rank == 0) {
    // the session's three threads: this one is the op thread
    RingSession session(count, OP_SUM, /*divisor=*/2);
    std::thread push([&] {
      for (size_t k = 0; k < count; ++k) {
        while (packed.load(std::memory_order_acquire) <= k)
          std::this_thread::yield();
        if (!session.push(leaf.data() + k * piece_elems, piece_elems * 2,
                          DT_BF16))
          break;  // the session failed: the run says why
      }
    });
    std::thread gather([&] {
      for (size_t k = 0; k < count; ++k) {
        std::string why;
        if (session.wait(k, 120.0, &why) != RingSession::kRung) {
          fail("phase P session piece " + std::to_string(k) + ": " + why);
          break;
        }
        verify(k);  // while the later pieces are still written and rung
      }
    });
    try {
      comm->run_session(session);
    } catch (const std::exception& ex) {
      fail(std::string("phase P session: ") + ex.what());
    }
    push.join();
    gather.join();
  } else {
    for (size_t k = 0; k < count; ++k) {
      while (packed.load(std::memory_order_acquire) <= k)
        std::this_thread::yield();
      void* seg = leaf.data() + k * piece_elems;
      uint64_t len = piece_elems * 2;
      try {
        comm->allreduce_iov(&seg, &len, 1, DT_BF16, OP_SUM, /*divisor=*/2);
      } catch (const std::exception& ex) {
        fail("phase P piece " + std::to_string(k) + ": " + ex.what());
        break;
      }
    }
  }
  pack.join();
  for (size_t k = 0; k < count; ++k) verify(k);
}

// ---------------------------------------------------------------------------
// Phase C: ring sessions with abort() fired into them
// ---------------------------------------------------------------------------

constexpr size_t kSessionPieces = 12;
std::atomic<size_t> g_session_rung{0};  // pieces reported rung, every rank's
constexpr size_t kSessionFloats = 96 << 10;  // 384 KiB: two lanes engage

// One session on this rank: a pusher (naps between pieces, so the run waits
// for pushes), a waiter, and this thread as the op thread.  Returns how many
// pieces reported rung (each verified).  `expect_all`: a failure is a fault.
size_t phase_c_session(Communicator* comm, int rank, bool expect_all) {
  std::vector<std::vector<float>> pieces(
      kSessionPieces, std::vector<float>(kSessionFloats));
  RingSession session(kSessionPieces, OP_SUM, /*divisor=*/kWorld);
  std::thread push([&] {
    for (size_t k = 0; k < kSessionPieces; ++k) {
      // a piece a rank and a place: (rank + 1) * (k + 1)
      std::fill(pieces[k].begin(), pieces[k].end(),
                static_cast<float>((rank + 1) * (k + 1)));
      std::this_thread::sleep_for(std::chrono::microseconds(300 * (rank + 1)));
      if (!session.push(pieces[k].data(), kSessionFloats * 4, DT_F32)) break;
    }
    session.close();  // a push may be missing: the run must not wait for it
  });
  size_t rung = 0;
  std::thread gather([&] {
    for (size_t k = 0; k < kSessionPieces; ++k) {
      std::string why;
      auto got = session.wait(k, 60.0, &why);
      if (got == RingSession::kTimedOut) {
        fail("phase C wait for piece " + std::to_string(k) + " never returned");
        return;
      }
      if (got != RingSession::kRung) {
        if (expect_all)
          fail("phase C clean session piece " + std::to_string(k) + ": " + why);
        return;
      }
      const float want =
          static_cast<float>(expected_sum(kWorld)) * (k + 1) / kWorld;
      for (float v : pieces[k])
        if (v != want) {
          fail("phase C piece " + std::to_string(k) + " reported rung, corrupt");
          break;
        }
      ++rung;
      g_session_rung.fetch_add(1);
    }
  });
  try {
    comm->run_session(session);
  } catch (const std::exception& ex) {
    if (expect_all) fail(std::string("phase C clean session: ") + ex.what());
  }
  push.join();
  gather.join();
  return rung;
}

}  // namespace

int main(int argc, char** argv) {
  // two lanes: the per-lane worker pool and striped framing run throughout;
  // an abort mid-striped-op kills every lane to the peer at once (the
  // native tier's lane-failure story)
  ::setenv("TORCHFT_RING_LANES", "2", 1);

  StoreServer store("127.0.0.1:0");
  std::string addr = "127.0.0.1:" + std::to_string(store.port());

  std::vector<std::unique_ptr<Communicator>> comms;
  for (int r = 0; r < kWorld; ++r)
    comms.push_back(std::make_unique<Communicator>(kOpTimeoutS));

  if (argc > 1 && std::string(argv[1]) == "pieces") {
    const size_t count = argc > 2 ? std::strtoul(argv[2], nullptr, 10) : 89;
    const size_t mib = argc > 3 ? std::strtoul(argv[3], nullptr, 10) : 16;
    // the shipped lane count: auto, with no link emulated
    ::unsetenv("TORCHFT_RING_LANES");
    ::unsetenv("TORCHFT_NET_EMU");
    std::vector<std::thread> ranks;
    for (int r = 0; r < 2; ++r)
      ranks.emplace_back(phase_p_rank, comms[r].get(), r, addr, count,
                         (mib << 20) / 2);
    for (auto& t : ranks) t.join();
    const size_t lanes = comms[0]->lanes();
    if (lanes != kUnshapedAutoLanes)
      fail("phase P ran at " + std::to_string(lanes) + " lanes, auto is " +
           std::to_string(kUnshapedAutoLanes));
    comms.clear();
    std::printf("stress_comm: phase P done (%zu pieces of %zu MiB x 2 ranks, "
                "%zu lanes, %d failure(s))\n", count, mib, lanes,
                g_failures.load());
    return g_failures.load() != 0;
  }

  // --- phase A ---------------------------------------------------------
  {
    std::vector<std::thread> ranks;
    for (int r = 0; r < kWorld; ++r)
      ranks.emplace_back(phase_a_rank, comms[r].get(), r, addr);
    for (auto& t : ranks) t.join();
    std::printf("stress_comm: phase A done (%d iters x %d ranks)\n",
                kPhaseAIters, kWorld);
  }

  // --- phase B ---------------------------------------------------------
  {
    BState st;
    const uint64_t all_ranks = (uint64_t(1) << kWorld) - 1;
    std::vector<std::thread> ranks;
    for (int r = 0; r < kWorld; ++r)
      ranks.emplace_back(phase_b_rank, comms[r].get(), r, &st);

    int verified_epochs = 0;
    for (int e = 1; e <= kPhaseBEpochs; ++e) {
      // yank the epoch out from under the op threads: abort mid-op from
      // this foreign thread, then re-rendezvous every communicator from
      // fresh controller threads WHILE the superseded ops unwind — the
      // torn-EpochIO overlap, continuously
      for (auto& c : comms) c->abort();
      std::vector<std::thread> cfg;
      for (int r = 0; r < kWorld; ++r)
        cfg.emplace_back([&, r] {
          try {
            comms[r]->configure(addr + "/stress_b_" + std::to_string(e), r,
                                kWorld);
          } catch (const std::exception& ex) {
            fail("phase B configure rank " + std::to_string(r) + " epoch " +
                 std::to_string(e) + ": " + ex.what());
          }
        });
      for (auto& t : cfg) t.join();
      st.epoch.store(e);
      st.settled.store(0);  // after the publish: stale-epoch bits can't leak in
      // wait (bounded) for every rank to land one VERIFIED allreduce on
      // this epoch before tearing it down again
      auto deadline =
          std::chrono::steady_clock::now() + std::chrono::seconds(60);
      while (st.settled.load() != all_ranks &&
             std::chrono::steady_clock::now() < deadline)
        std::this_thread::sleep_for(std::chrono::milliseconds(2));
      if (st.settled.load() == all_ranks)
        ++verified_epochs;
      else
        fail("phase B epoch " + std::to_string(e) + " never settled");
      // a short overlap window with ops back in flight before the next yank
      std::this_thread::sleep_for(std::chrono::milliseconds(10));
    }
    st.stop.store(true);
    for (auto& c : comms) c->abort();  // unblock any parked op
    for (auto& t : ranks) t.join();
    std::printf("stress_comm: phase B done (%d/%d epochs verified)\n",
                verified_epochs, kPhaseBEpochs);
    check(verified_epochs == kPhaseBEpochs, "phase B epochs missed");
  }

  // --- phase C ---------------------------------------------------------
  {
    constexpr int kRounds = 4;
    size_t rung_under_abort = 0;
    for (int e = 1; e <= kRounds; ++e) {
      std::vector<std::thread> cfg;
      for (int r = 0; r < kWorld; ++r)
        cfg.emplace_back([&, r] {
          try {
            comms[r]->configure(addr + "/stress_c_" + std::to_string(e), r,
                                kWorld);
          } catch (const std::exception& ex) {
            fail("phase C configure rank " + std::to_string(r) + ": " +
                 ex.what());
          }
        });
      for (auto& t : cfg) t.join();
      // a clean session first: every piece of every rank rung and verified
      std::vector<size_t> rung(kWorld, 0);
      std::vector<std::thread> ranks;
      for (int r = 0; r < kWorld; ++r)
        ranks.emplace_back(
            [&, r] { rung[r] = phase_c_session(comms[r].get(), r, true); });
      for (auto& t : ranks) t.join();
      for (int r = 0; r < kWorld; ++r)
        check(rung[r] == kSessionPieces, "phase C clean session stopped short");
      // then one with abort() fired into it, a piece later every round
      ranks.clear();
      g_session_rung.store(0);
      for (int r = 0; r < kWorld; ++r)
        ranks.emplace_back(
            [&, r] { rung[r] = phase_c_session(comms[r].get(), r, false); });
      auto until = std::chrono::steady_clock::now() + std::chrono::seconds(30);
      while (g_session_rung.load() < size_t(kWorld) * e &&
             std::chrono::steady_clock::now() < until)
        std::this_thread::sleep_for(std::chrono::microseconds(100));
      for (auto& c : comms) c->abort();
      for (auto& t : ranks) t.join();
      for (int r = 0; r < kWorld; ++r) rung_under_abort += rung[r];
    }
    std::printf("stress_comm: phase C done (%d rounds; %zu of %zu pieces rung "
                "before the aborts)\n", kRounds, rung_under_abort,
                size_t(kRounds) * kWorld * kSessionPieces);
  }

  comms.clear();
  if (g_failures.load() != 0) {
    std::fprintf(stderr, "stress_comm: %d failure(s)\n", g_failures.load());
    return 1;
  }
  std::printf("stress_comm: OK\n");
  return 0;
}
