// Standalone throughput benchmark for the native communicator (no Python):
//   ./bench_comm            — forks store + 2 ranks, 256MB p2p + ring,
//                             and the averaging ring beside the summing one
//                             (the difference: what the division costs the
//                             last reduce step's add on the lanes, no pass)
//   ./bench_comm pieces [MB] — a step's gradient bytes (973 MB of bfloat16,
//                             the one-chip two-group cell's) rung with the
//                             divisor whole and in pieces of 64, 16 and 4
//                             MiB, at the lanes TORCHFT_RING_LANES names;
//                             a second line a piece size rings the same
//                             pieces through ONE session (run_session), all
//                             pushed up front: the ring alone, with what a
//                             ring pays once a piece paid once
// Both print, beside the wall time, where the ring says its time went
// (comm.h EpochIO's seven counters of nanoseconds, and a session's wait for
// the next push): the terms a traced two-group cell reports as ring_rx_ms
// ... ring_tail_ms.  `average` is the
// stand-alone division pass, which a ring of two never takes: it reads 0.0
// and the division lies in a lane's `add` (the before/after of moving it
// there is `pieces` from the two commits' binaries).
#include <sys/wait.h>
#include <unistd.h>

#include <chrono>
#include <cstdio>
#include <cstring>
#include <vector>

#include "comm.h"
#include "store.h"

using namespace tpuft;

// The epoch's seven time counters in milliseconds, a lane's three as the
// MEAN over the lanes that sent bytes (the rule ddp.allreduce_pytree puts
// on DDP_SYNC); differenced over a stretch of rings they say where it went.
struct RingTimes {
  double ms[8] = {0, 0, 0, 0, 0, 0, 0, 0};  // rx add tx | reduce average gather tail wait_push
  uint64_t tx_bytes[64] = {0};
  uint64_t lane_ns[3][64] = {{0}};
  uint64_t ring_ns[5] = {0, 0, 0, 0, 0};
  size_t lanes = 0;

  static RingTimes read(const Communicator& comm) {
    RingTimes t;
    uint64_t rx[64], stalls[64];
    uint64_t* lane_ns[3] = {t.lane_ns[0], t.lane_ns[1], t.lane_ns[2]};
    t.lanes = std::min<size_t>(
        64, comm.lane_stats(t.tx_bytes, rx, stalls, 64, lane_ns, t.ring_ns));
    return t;
  }
  // the stretch from `before` to this reading
  RingTimes since(const RingTimes& before) const {
    RingTimes d;
    size_t moved = 0;
    for (size_t i = 0; i < lanes; ++i) {
      if (tx_bytes[i] == before.tx_bytes[i]) continue;
      ++moved;
      for (int k = 0; k < 3; ++k)
        d.ms[k] += (lane_ns[k][i] - before.lane_ns[k][i]) / 1e6;
    }
    for (int k = 0; k < 3; ++k) d.ms[k] /= moved ? moved : 1;
    for (int k = 0; k < 5; ++k)
      d.ms[3 + k] = (ring_ns[k] - before.ring_ns[k]) / 1e6;
    d.lanes = moved;
    return d;
  }
  void print(int rank, const char* what, double wall_s) const {
    std::printf("rank %d %s: wall %.1f ms = reduce %.1f + average %.1f + "
                "gather %.1f (+ %.1f outside the phases, ring_wait_push %.1f "
                "of it); of the phases a lane (mean of %zu) rx %.1f, add %.1f, "
                "tx %.1f; tail %.1f\n",
                rank, what, wall_s * 1e3, ms[3], ms[4], ms[5],
                wall_s * 1e3 - ms[3] - ms[4] - ms[5], ms[7], lanes, ms[0],
                ms[1], ms[2], ms[6]);
  }
};

static void run_rank(const std::string& store_addr, int rank) {
  Communicator comm(60.0);
  comm.configure(store_addr + "/bench", rank, 2);
  const size_t N = 256ull << 20;
  std::vector<uint8_t> payload(N, 7);

  // p2p warm + timed
  if (rank == 0) {
    comm.send(payload.data(), N, 1, 1);
    auto t0 = std::chrono::steady_clock::now();
    comm.send(payload.data(), N, 1, 2);
    double dt = std::chrono::duration<double>(
                    std::chrono::steady_clock::now() - t0).count();
    std::printf("send 256MB: %.3fs (%.2f GB/s)\n", dt, N / dt / 1e9);
  } else {
    comm.recv_dynamic(0, 1);
    auto t0 = std::chrono::steady_clock::now();
    auto data = comm.recv_dynamic(0, 2);
    double dt = std::chrono::duration<double>(
                    std::chrono::steady_clock::now() - t0).count();
    std::printf("recv 256MB: %.3fs (%.2f GB/s)\n", dt, data.size() / dt / 1e9);
  }

  // ring allreduce 128MB f32
  std::vector<float> buf(32 << 20, 1.0f);
  comm.allreduce(buf.data(), buf.size() * 4, DT_F32, OP_SUM);  // warm
  auto t0 = std::chrono::steady_clock::now();
  comm.allreduce(buf.data(), buf.size() * 4, DT_F32, OP_SUM);
  double dt = std::chrono::duration<double>(
                  std::chrono::steady_clock::now() - t0).count();
  std::printf("rank %d ring 128MB: %.3fs (%.2f GB/s effective)\n", rank, dt,
              buf.size() * 4.0 / dt / 1e9);

  // The ring that hands back the average beside the one that hands back the
  // sum, 256MB of bfloat16 (a gradient bucket): what the division adds to
  // the last reduce step's add (a true division an element of the owned
  // half, on the lanes' threads beside their recv), no pass of its own.
  // Best of kRounds each, interleaved.
  constexpr int kRounds = 5;
  const size_t M = 128ull << 20;  // elements
  std::vector<uint16_t> grad(M);
  auto timed = [&](uint64_t divisor) {
    for (size_t i = 0; i < M; ++i)
      grad[i] = f32_to_bf16(static_cast<float>((i * 2654435761u >> 8) % 2001) -
                            1000.0f);
    auto a = std::chrono::steady_clock::now();
    comm.allreduce(grad.data(), M * 2, DT_BF16, OP_SUM, divisor);
    return std::chrono::duration<double>(std::chrono::steady_clock::now() - a)
        .count();
  };
  double best[2] = {1e9, 1e9};
  RingTimes best_times;  // of the averaging ring's best round
  for (int round = 0; round <= kRounds; ++round) {
    double sum_s = timed(0);
    RingTimes before = RingTimes::read(comm);
    double got[2] = {sum_s, timed(2)};
    if (round == 0) continue;  // warm
    if (got[1] < best[1]) best_times = RingTimes::read(comm).since(before);
    for (int k = 0; k < 2; ++k) best[k] = std::min(best[k], got[k]);
  }
  std::printf("rank %d ring 256MB bf16: sum %.3fs, average %.3fs (%+.0f ms: "
              "the division inside the add)\n",
              rank, best[0], best[1], (best[1] - best[0]) * 1e3);
  best_times.print(rank, "ring 256MB bf16 average", best[1]);
  std::fflush(stdout);  // the forked rank leaves by _exit
}

// What a ring's set-up costs against its bytes, and what a lane buys: the
// same bytes as one ring and as ddp.allreduce_pytree's pieces, in place,
// one after another on this thread (the op thread's chain); the second
// pass of two is the reading.
static void run_pieces(const std::string& store_addr, int rank, size_t mb) {
  Communicator comm(60.0);
  comm.configure(store_addr + "/pieces", rank, 2);
  const size_t lanes = comm.lanes();
  const size_t elems = mb * 500000;  // bfloat16
  std::vector<uint16_t> grad(elems, f32_to_bf16(1.0f));  // the average of ones
  for (size_t mib : {0, 64, 16, 4}) {
    const size_t piece = mib ? (mib << 20) / 2 : elems;
    size_t rings = 0;
    double dt = 0;
    RingTimes before;
    for (int pass = 0; pass < 2; ++pass) {
      rings = 0;
      before = RingTimes::read(comm);
      auto t0 = std::chrono::steady_clock::now();
      for (size_t off = 0; off < elems; off += piece, ++rings)
        comm.allreduce(grad.data() + off, std::min(piece, elems - off) * 2,
                       DT_BF16, OP_SUM, /*divisor=*/2);
      dt = std::chrono::duration<double>(std::chrono::steady_clock::now() - t0)
               .count();
    }
    std::printf("rank %d lanes %zu: %zu MB as %zu ring(s) of %zu MiB: %.3fs, "
                "%.2f ms a ring\n", rank, lanes, mb, rings, mib, dt,
                dt * 1e3 / rings);
    RingTimes::read(comm).since(before).print(rank, "the pass", dt);
    // the same pieces as ONE call: a session with every piece pushed up
    // front, so the op thread never waits and the line reads the ring alone
    for (int pass = 0; pass < 2; ++pass) {
      RingSession session(rings, OP_SUM, /*divisor=*/2);
      for (size_t off = 0; off < elems; off += piece)
        session.push(grad.data() + off, std::min(piece, elems - off) * 2,
                     DT_BF16);
      before = RingTimes::read(comm);
      auto t0 = std::chrono::steady_clock::now();
      comm.run_session(session);
      dt = std::chrono::duration<double>(std::chrono::steady_clock::now() - t0)
               .count();
    }
    std::printf("rank %d lanes %zu: the same %zu piece(s) through one "
                "session: %.3fs, %.2f ms a piece\n", rank, lanes, rings, dt,
                dt * 1e3 / rings);
    RingTimes::read(comm).since(before).print(rank, "the session", dt);
  }
  std::fflush(stdout);
}

int main(int argc, char** argv) {
  StoreServer store("127.0.0.1:0");
  std::string addr = "127.0.0.1:" + std::to_string(store.port());
  const bool pieces = argc > 1 && std::string(argv[1]) == "pieces";
  const size_t mb = argc > 2 ? std::strtoul(argv[2], nullptr, 10) : 973;
  auto run = [&](int rank) {
    if (pieces) run_pieces(addr, rank, mb);
    else run_rank(addr, rank);
  };
  pid_t pid = fork();
  if (pid == 0) {
    run(1);
    _exit(0);
  }
  run(0);
  int status = 0;
  waitpid(pid, &status, 0);
  return 0;
}
