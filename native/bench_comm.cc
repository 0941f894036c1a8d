// Standalone throughput benchmark for the native communicator (no Python):
//   ./bench_comm            — forks store + 2 ranks, 256MB p2p + ring,
//                             and the averaging ring beside the summing one
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

int main() {
  StoreServer store("127.0.0.1:0");
  std::string addr = "127.0.0.1:" + std::to_string(store.port());
  pid_t pid = fork();
  if (pid == 0) {
    run_rank(addr, 1);
    _exit(0);
  }
  run_rank(addr, 0);
  int status = 0;
  waitpid(pid, &status, 0);
  return 0;
}
