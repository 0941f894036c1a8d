// Standalone throughput benchmark for the native communicator (no Python):
//   ./bench_comm            — forks store + 2 ranks, 256MB p2p + ring,
//                             and the averaging ring beside the summing one
//   ./bench_comm pieces [MB] — a step's gradient bytes (973 MB of bfloat16,
//                             the one-chip two-group cell's) rung with the
//                             divisor whole and in pieces of 64, 16 and 4
//                             MiB, at the lanes TORCHFT_RING_LANES names
#include <sys/wait.h>
#include <unistd.h>

#include <chrono>
#include <cstdio>
#include <cstring>
#include <vector>

#include "comm.h"
#include "store.h"

using namespace tpuft;

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
  // sum, 256MB of bfloat16 (a gradient bucket): what the owner's one pass
  // over its half costs between the phases.  Best of kRounds each,
  // interleaved.
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
  for (int round = 0; round <= kRounds; ++round) {
    double got[2] = {timed(0), timed(2)};
    if (round == 0) continue;  // warm
    for (int k = 0; k < 2; ++k) best[k] = std::min(best[k], got[k]);
  }
  std::printf("rank %d ring 256MB bf16: sum %.3fs, average %.3fs (+%.0f ms)\n",
              rank, best[0], best[1], (best[1] - best[0]) * 1e3);
  std::fflush(stdout);  // the forked rank leaves by _exit
}

// What a ring's set-up costs against its bytes, and what a lane buys: the
// same bytes as one ring and as ddp.allreduce_pytree's pieces, in place,
// one after another on this thread (the op thread's chain); the second
// pass of two is the reading.
static void run_pieces(const std::string& store_addr, int rank, size_t mb) {
  Communicator comm(60.0);
  comm.configure(store_addr + "/pieces", rank, 2);
  uint64_t tx[64], rx[64], stalls[64];
  const size_t lanes = comm.lane_stats(tx, rx, stalls, 64);
  const size_t elems = mb * 500000;  // bfloat16
  std::vector<uint16_t> grad(elems, f32_to_bf16(1.0f));  // the average of ones
  for (size_t mib : {0, 64, 16, 4}) {
    const size_t piece = mib ? (mib << 20) / 2 : elems;
    size_t rings = 0;
    double dt = 0;
    for (int pass = 0; pass < 2; ++pass) {
      rings = 0;
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
