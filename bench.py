"""Benchmark: the north-star measurement (BASELINE.json).

Four phases, all on the same backend.  That backend is the TPU: without
one the bench exits non-zero, and it runs on the CPU only when
``TPUFT_BENCH_PLATFORM=cpu`` says so.  A chip belongs to one process, and
phase A takes it for this one, so on a TPU backend the multi-process phases
(B, C, D: one worker process per replica, plus warm standbys) are refused
up front; run with ``TPUFT_BENCH_SKIP_FLEET=1`` there until thread replicas
replace them (ROADMAP S1).

A. **ws=1 overhead + MFU** — tokens/sec/chip for the plain train step
   (ALL measured steps scan-chained inside ONE jit, which is what MFU is
   computed from) vs the full fault-tolerant stack (lighthouse + manager +
   per-step quorum/commit RPCs, a python step loop by design) in one
   process, on a ~0.8B-param Llama with the cheapest remat policy that
   fits (attn → ffn → layer OOM walk).  Reports absolute tokens/sec/chip,
   model TFLOP/s, and MFU against the chip's autodetected bf16 peak.
B. **fault-free fleet** — N replica-group subprocesses (default 3 on TPU),
   each a real Communicator + Manager + HTTP-heal stack doing replica-dim
   gradient averaging over the DCN ring, no failures.
C. **fleet under faults** — same fleet, but victims (rotating over replicas
   1..N-1; replica 0 is the measurement anchor) are SIGKILLed every K
   survivor steps and auto-respawned; each rejoining process heals live
   weights from a survivor.  Reports the with-faults/fault-free throughput
   ratio (the BASELINE >=0.95 target), mean heal-in seconds, and a
   per-phase **heal breakdown** (respawn / jax init / model build / join+
   rendezvous+transfer / first-step compile) from worker-side phase logs.
   The reference measures the same quantities in its manager integration
   harness (``torchft/manager_integ_test.py:340-430``).
D. **DiLoCo under churn** (BASELINE config 4) — N islands running
   Streaming DiLoCo (fragments, sync_every, τ delay) with kills timed to
   land inside the fragment-sync window; reports inner-step throughput
   ratio vs a fault-free DiLoCo fleet and the per-sync overhead
   (``torchft/local_sgd.py:175-795``).

The whole bench runs on the production tier by default: C++ lighthouse +
manager servers and the C++ data-plane communicator when
``native/libtpuft.so`` loads, Python otherwise (``"tier"`` in the output
records which; the reference likewise benches NCCL, not Gloo).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...extras}.
``value`` is the phase-C/phase-B ratio when the fleet phases complete, else
the phase-A ratio (and "faults" reports why).

Env knobs: TPUFT_BENCH_STEPS, TPUFT_BENCH_DIM, TPUFT_BENCH_LAYERS,
TPUFT_BENCH_SEQ, TPUFT_BENCH_BATCH, TPUFT_BENCH_HEAD_DIM,
TPUFT_BENCH_REMAT, TPUFT_BENCH_PLATFORM, TPUFT_BENCH_FLEET_STEPS,
TPUFT_BENCH_KILL_EVERY, TPUFT_BENCH_REPLICAS, TPUFT_BENCH_SKIP_FLEET,
TPUFT_BENCH_SKIP_DILOCO, TPUFT_BENCH_DILOCO_QUANT (0/1/auto),
TPUFT_BENCH_OUT (streaming artifact path),
TPUFT_BENCH_TOTAL_BUDGET_S (wall-clock bound; phases shrink/skip to fit.
Per-fleet deadline floors (120/180 s, DiLoCo 90/180 s) are capped at what
remains once the budget is spent, so the hard worst case a driver must
allow before hard-killing is the budget + the one fleet floor that
straddles the deadline (<= 180 s) + teardown),
TPUFT_BENCH_HEAL_TRANSPORT (comm|http — heal over the collective fabric
vs the reference-parity HTTP server), TPUFT_PEAK_TFLOPS, TORCHFT_TIER.

Output contract: stdout's LAST line is one compact headline JSON (<=~1 KB,
survives a 2000-char tail capture); the full nested artifact streams to
``bench_out.json`` (or TPUFT_BENCH_OUT) as each phase completes.
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

# per-chip bf16 peak TFLOP/s by device_kind substring (first match wins)
_TPU_PEAKS: List[Tuple[str, float]] = [
    ("v6e", 918.0),
    ("v6 lite", 918.0),
    ("v5e", 197.0),
    ("v5 lite", 197.0),
    ("v5litepod", 197.0),
    ("v5p", 459.0),
    ("v4", 275.0),
    ("v3", 123.0),
    ("v2", 46.0),
]


def _peak_tflops(device) -> Optional[float]:
    """bf16 peak of a TPU ``device`` (None off TPU, where MFU means
    nothing).  An unknown TPU ``device_kind`` is an error: a guessed peak
    puts a wrong MFU in the artifact."""
    env = os.environ.get("TPUFT_PEAK_TFLOPS")
    if env:
        return float(env)
    if device.platform != "tpu":
        return None
    kind = (device.device_kind or "").lower()
    for pat, peak in _TPU_PEAKS:
        if pat in kind:
            return peak
    raise ValueError(
        f"no bf16 peak known for device_kind {device.device_kind!r}; add it "
        "to _TPU_PEAKS with its source, or set TPUFT_PEAK_TFLOPS"
    )


def _configure_jax(platform: Optional[str]) -> None:
    import jax

    from torchft_tpu.utils.compile_cache import configure_compile_cache

    if platform:
        jax.config.update("jax_platforms", platform)
    # persistent compile cache: bench reruns (and respawned fleet workers)
    # skip the slow first compile
    configure_compile_cache()


def _sizes(on_cpu: bool) -> Dict[str, int]:
    """Workload dims; an explicit CPU run shrinks so the protocol still gets
    exercised in minutes."""

    def env_int(name: str, cpu: int, tpu: int) -> int:
        return int(os.environ.get(name, cpu if on_cpu else tpu))

    return {
        # phase A: a ~0.8B-param Llama (dim 2048 x 16 layers, head_dim 128,
        # seq 2048) — big enough that MXU efficiency, not protocol RPC,
        # decides the number; remat makes it fit single-chip HBM
        "steps": env_int("TPUFT_BENCH_STEPS", 8, 30),
        "dim": env_int("TPUFT_BENCH_DIM", 256, 2048),
        "layers": env_int("TPUFT_BENCH_LAYERS", 4, 16),
        "seq": env_int("TPUFT_BENCH_SEQ", 256, 2048),
        "batch": env_int("TPUFT_BENCH_BATCH", 4, 8),
        "head_dim": env_int("TPUFT_BENCH_HEAD_DIM", 64, 128),
        "remat": env_int("TPUFT_BENCH_REMAT", 0, 1),
        # CPU fleet sizes amortize heal cost honestly: at 48 steps
        # and a kill every 14 the per-100-step normalization sees 3 kills
        # (14/28/42 — a 16-step cadence lands the third ON the target step
        # and loses it) averaged over a real steady phase rather than 2
        # kills dominating a 16-step blip (the round-4 artifact's 0.9485)
        "fleet_steps": env_int("TPUFT_BENCH_FLEET_STEPS", 48, 100),
        "kill_every": env_int("TPUFT_BENCH_KILL_EVERY", 14, 25),
        # 3 replicas even on CPU: victim rotation + the cold last victim
        # record BOTH heal paths (standby + cold) in one artifact
        "replicas": env_int("TPUFT_BENCH_REPLICAS", 3, 3),
        # fleet phases measure the FT mechanics (quorum, DCN ring, kill,
        # heal); a smaller model keeps per-step host<->device traffic sane
        "fleet_dim": env_int("TPUFT_BENCH_FLEET_DIM", 256, 256),
        "fleet_layers": env_int("TPUFT_BENCH_FLEET_LAYERS", 4, 4),
        "fleet_seq": env_int("TPUFT_BENCH_FLEET_SEQ", 256, 512),
        "fleet_batch": env_int("TPUFT_BENCH_FLEET_BATCH", 4, 8),
        "fleet_head_dim": 64,
        # warm standby for killable replicas: a parked pre-initialized spare
        # is promoted on kill, cutting heal-in from cold-start seconds to
        # join+transfer seconds (0 measures the cold path instead)
        "standby": env_int("TPUFT_BENCH_STANDBY", 1, 1),
        # phase D (DiLoCo): inner steps + streaming-fragment schedule;
        # >= 3 in-window kills on EVERY platform so the churn ratio is
        # never a sample-of-one (rounds 3+4 shipped single-kill artifacts)
        "diloco_steps": env_int("TPUFT_BENCH_DILOCO_STEPS", 48, 96),
        "diloco_sync_every": env_int("TPUFT_BENCH_DILOCO_SYNC", 8, 8),
        "diloco_fragments": 2,
        "diloco_sync_delay": 2,
        "diloco_kills": env_int("TPUFT_BENCH_DILOCO_KILLS", 3, 3),
    }


def _quant_kind_or_error() -> str:
    """The validated wire kind actually in effect (the workers' Manager
    would reject an invalid one at startup) — never the raw env string."""
    from torchft_tpu.quantization import quant_kind

    try:
        return quant_kind()
    except ValueError as e:
        return f"invalid ({e})"


def _diloco_quant_env() -> str:
    """The DiLoCo quantized-sync knob: "0" / "1" force the wire; the
    default "auto" has phase D measure BOTH fault-free and gate the churn
    run on the one that actually costs less per sync on this link
    (quantization spends host cycles that a fat loopback never pays back —
    the reference keeps it opt-in, ``manager.py:457-468``)."""
    v = os.environ.get("TPUFT_BENCH_DILOCO_QUANT", "auto").strip().lower()
    return v if v in ("0", "1") else "auto"


def _outer_shard_mode_env() -> str:
    """Canonical TORCHFT_OUTER_SHARD mode via the SAME parser the workers
    use (``local_sgd._outer_shard_mode``), so every accepted spelling —
    'off'/'false' included — labels the artifact the way the fleet actually
    ran.  An unparseable value falls back to the raw string: it will never
    equal "0", and the workers crash on it loudly anyway."""
    from torchft_tpu.local_sgd import _outer_shard_mode

    try:
        return _outer_shard_mode()
    except ValueError:
        return os.environ.get("TORCHFT_OUTER_SHARD", "auto").strip().lower()


def _build_model(
    sizes: Dict[str, int], fleet: bool = False, remat_mode: str = "none"
):
    import jax.numpy as jnp

    from torchft_tpu.models.llama import Llama, LlamaConfig

    prefix = "fleet_" if fleet else ""
    dim = sizes[f"{prefix}dim"]
    head_dim = sizes[f"{prefix}head_dim"]
    n_heads = max(1, dim // head_dim)
    config = LlamaConfig(
        vocab_size=8192,
        dim=dim,
        n_layers=sizes[f"{prefix}layers"],
        n_heads=n_heads,
        n_kv_heads=max(1, n_heads // 4),
        ffn_hidden=dim * 3,
        max_seq_len=sizes[f"{prefix}seq"],
        dtype=jnp.bfloat16,
        remat_mode="none" if fleet else remat_mode,
    )
    return Llama(config), config


# extra hardware FLOPs each remat policy re-runs in the backward, as a
# multiplier on the 6N/token convention (fwd 2N + bwd 4N): "layer" re-runs
# the whole forward (+2N -> 8/6); "ffn" re-runs the FFN forward (~78% of
# the weight-matmul FLOPs at ffn_hidden = 3*dim, GQA/4 -> ~7.56/6);
# "attn" re-runs the attention forward (~22% + scores -> ~6.7/6)
_REMAT_HW_FACTOR = {
    "none": 1.0,
    "attn": 6.7 / 6.0,
    "ffn": 7.56 / 6.0,
    "layer": 8.0 / 6.0,
}


def _phase_a_modes(sizes: Dict[str, int]) -> List[str]:
    """Remat-mode preference for phase A.  Explicit env wins; otherwise,
    when remat is requested, try cheapest-recompute first and let the OOM
    fallback in :func:`run_single` walk toward "layer" — the mode that is
    known to fit.  Recompute tax: attn ~12%, ffn ~26%, layer ~33%."""
    env = os.environ.get("TPUFT_BENCH_REMAT_MODE", "")
    if env:
        return [env]
    if not sizes.get("remat"):
        return ["none"]
    return ["attn", "ffn", "layer"]


# --------------------------------------------------------------------------
# fleet worker (subprocess entry: `python bench.py --worker`)
# --------------------------------------------------------------------------


class _EventLog:
    """Line-buffered JSONL event/phase log; survives SIGKILL mid-line (the
    reader skips torn lines).  Every record carries the writer's pid: a
    replica group's log interleaves multiple process incarnations (active,
    killed, promoted standby, re-warmed spare), and heal attribution must
    only read the incarnation that actually rejoined."""

    def __init__(self, path: str) -> None:
        self._f = open(path, "a", buffering=1)
        self._pid = os.getpid()

    def phase(self, name: str, **extra: Any) -> None:
        rec = {"phase": name, "ts": time.time(), "pid": self._pid}
        rec.update(extra)
        self._f.write(json.dumps(rec) + "\n")

    def step(self, step: int, **extra: Any) -> None:
        rec = {"step": step, "ts": time.time(), "pid": self._pid}
        rec.update(extra)
        self._f.write(json.dumps(rec) + "\n")


def worker_main() -> None:
    t_proc = time.time()
    rg = int(os.environ["REPLICA_GROUP_ID"])
    target = int(os.environ["TPUFT_BENCH_TARGET_STEPS"])
    events_dir = os.environ["TPUFT_BENCH_EVENTS_DIR"]
    mode = os.environ.get("TPUFT_BENCH_MODE", "ddp")
    ev = _EventLog(os.path.join(events_dir, f"replica_{rg}.jsonl"))
    ev.phase("proc_start", ts_override=t_proc)
    stop_path = os.path.join(events_dir, "stop")

    _configure_jax(os.environ.get("TPUFT_BENCH_WORKER_PLATFORM") or None)

    import jax
    import jax.numpy as jnp
    import optax

    from torchft_tpu import tier as tier_mod
    from torchft_tpu.manager import Manager

    device = jax.devices()[0]  # forces backend init
    ev.phase("jax_ready")

    sizes = {
        f"fleet_{k}": int(os.environ[f"TPUFT_BENCH_{k.upper()}"])
        for k in ("dim", "layers", "seq", "batch", "head_dim")
    }
    model, config = _build_model(sizes, fleet=True)
    # identical init on every replica (the reference seeds identically in its
    # examples; init_sync covers the general case)
    params = jax.device_put(model.init(jax.random.PRNGKey(0)), device)
    inner_tx = optax.adamw(1e-3)
    holder = {"params": params, "opt_state": jax.jit(inner_tx.init)(params)}

    # distinct per-replica data so the replica-dim average does real work
    key = jax.random.PRNGKey(1000 + rg)
    batch_shape = (sizes["fleet_batch"], sizes["fleet_seq"])
    batches = []
    for i in range(4):
        k = jax.random.fold_in(key, i)
        tokens = jax.random.randint(k, batch_shape, 0, config.vocab_size)
        batches.append(
            (jax.device_put(tokens, device), jnp.roll(tokens, -1, axis=1))
        )
    grad_step = jax.jit(jax.value_and_grad(model.loss))
    ev.phase("model_ready")

    gate = os.environ.get("TPUFT_STANDBY_GATE")
    if gate:
        # warm standby (launcher promotes us on the active twin's death):
        # pay the compile + first-execution cost NOW, then park.  A standby
        # must not touch the quorum while parked — the Manager is only
        # constructed after promotion.
        _loss, grads = grad_step(holder["params"], batches[0])
        jax.block_until_ready(grads)
        ev.phase("standby_parked")
        while not os.path.exists(gate) and not os.path.exists(stop_path):
            time.sleep(0.05)
        if os.path.exists(stop_path):
            return
        ev.phase("standby_promoted")

    tier = tier_mod.default_tier()
    comm = tier_mod.make_communicator(timeout_s=30.0)  # data-plane dispatch
    transport = None
    if os.environ.get("TPUFT_BENCH_HEAL_TRANSPORT", "comm") == "comm":
        # heal over the collective fabric (CommTransport) instead of HTTP:
        # same wire the gradients ride, and faster per transfer under
        # multi-replica contention on the CPU runs so far — HTTP stays
        # selectable for the reference-parity path
        from torchft_tpu.checkpointing.comm_transport import CommTransport

        transport = CommTransport(comm, timeout=60.0)
    manager = Manager(
        comm=comm,
        load_state_dict=lambda s: holder.update(s),
        state_dict=lambda: dict(holder),
        min_replica_size=1,
        replica_id=f"bench_{rg}",
        use_async_quorum=(mode == "ddp"),
        server_cls=tier_mod.manager_server_cls(tier),
        checkpoint_transport=transport,
    )
    ev.phase("manager_ready", tier=tier)

    if mode == "diloco":
        _worker_diloco(ev, manager, holder, grad_step, inner_tx, batches,
                       target, stop_path)
    else:
        _worker_ddp(ev, manager, holder, grad_step, inner_tx, batches,
                    target, stop_path)
    manager.shutdown()


def _worker_ddp(ev, manager, holder, grad_step, tx, batches, target,
                stop_path) -> None:
    from torchft_tpu.ddp import ft_allreduce
    from torchft_tpu.optim import OptimizerWrapper

    opt = OptimizerWrapper(manager, tx)
    first = True
    first_iter = True
    # the parent ends the phase via the stop file (so a healing victim gets
    # to rejoin even after the survivor passed the measurement target);
    # the hard cap is a runaway backstop
    while not os.path.exists(stop_path) and manager.current_step() < target * 5:
        opt.start_step()
        if first_iter:
            ev.phase("first_started")
        batch = batches[manager.current_step() % len(batches)]
        loss, grads = grad_step(holder["params"], batch)
        if first_iter:
            # sub-attribute the join-to-first-commit window (the round-4
            # breakdown left most of it in one opaque bucket): grads ready
            # (first-step compile + compute), quorum ready (join window +
            # rendezvous/configure + heal transfer, further split by the
            # Manager's own timings), residual = allreduce wire +
            # should_commit barrier + weight update.  One-shot: the heal
            # work happens on the FIRST iteration even when the commit
            # lands on a later one
            jax.block_until_ready(grads)
            ev.phase("first_grads_ready")
            try:
                manager.wait_quorum()
            except Exception:  # noqa: BLE001 — instrumentation must not
                # change failure semantics: Manager.allreduce funnels this
                # same error into a discarded step; a raise here would kill
                # the worker and corrupt the very heal being attributed
                pass
            ev.phase("first_quorum_ready")
            first_iter = False
        grads = ft_allreduce(manager, grads)
        if opt.step(holder, grads):
            if first:
                # quorum timings of the joining round: rpc (incl. barrier +
                # join window), rendezvous/configure, heal transfer
                ev.phase("first_commit", timings=manager.last_quorum_timings)
                first = False
            ev.step(manager.current_step())


def _worker_diloco(ev, manager, holder, grad_step, inner_tx, batches,
                   target, stop_path) -> None:
    import optax

    from torchft_tpu.local_sgd import DiLoCo

    sync_every = int(os.environ.get("TPUFT_BENCH_DILOCO_SYNC", "8"))
    fragments = int(os.environ.get("TPUFT_BENCH_DILOCO_FRAGMENTS", "2"))
    delay = int(os.environ.get("TPUFT_BENCH_DILOCO_DELAY", "2"))
    diloco = DiLoCo(
        manager,
        holder,
        outer_tx=optax.sgd(0.7, momentum=0.9, nesterov=True),
        sync_every=sync_every,
        num_fragments=fragments,
        fragment_sync_delay=delay,
        # quantized pseudogradient sync (int8 default, fp8 via
        # TORCHFT_QUANT_KIND) — the parent resolves the auto-gate and
        # passes a concrete 0/1 in this worker's env
        should_quantize=os.environ.get("TPUFT_BENCH_DILOCO_QUANT_WIRE", "0")
        == "1",
    )
    inner = 0
    first = True
    with diloco:
        while not os.path.exists(stop_path) and inner < target * 5:
            batch = batches[inner % len(batches)]
            loss, grads = grad_step(holder["params"], batch)
            updates, holder["opt_state"] = inner_tx.update(
                grads, holder["opt_state"], holder["params"]
            )
            holder["params"] = optax.apply_updates(holder["params"], updates)
            inner += 1
            committed = diloco.step()
            if committed is not None and first:
                ev.phase("first_commit", timings=manager.last_quorum_timings)
                first = False
            # cyc: position in the sync cycle (the parent times churn kills
            # to land in the fragment-sync window, cyc >= per_frag - delay);
            # outer: committed outer steps
            ev.step(
                inner,
                outer=manager.current_step(),
                cyc=diloco._local_step,
                sync=committed is not None,
            )


# --------------------------------------------------------------------------
# fleet orchestration (phases B, C, D)
# --------------------------------------------------------------------------


def _read_records(events_dir: str, rg: int) -> List[Dict[str, Any]]:
    path = os.path.join(events_dir, f"replica_{rg}.jsonl")
    out: List[Dict[str, Any]] = []
    try:
        with open(path) as f:
            for line in f:
                try:
                    out.append(json.loads(line))
                except json.JSONDecodeError:
                    continue  # torn final line of a SIGKILLed writer
    except FileNotFoundError:
        pass
    return out


def _steps_of(records: List[Dict[str, Any]]) -> List[Tuple[int, float]]:
    return [
        (r["step"], r["ts"]) for r in records if "step" in r and "ts" in r
    ]


def _phases_of(records: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
    out = []
    for r in records:
        if "phase" in r:
            r = dict(r)
            # proc_start records the pre-import timestamp explicitly
            if "ts_override" in r:
                r["ts"] = r.pop("ts_override")
            out.append(r)
    return out


def run_fleet(
    label: str,
    target_steps: int,
    sizes: Dict[str, int],
    worker_platform: Optional[str],
    kill_every: int = 0,
    replicas: int = 2,
    mode: str = "ddp",
    kill_in_sync_window: bool = False,
    max_kills: Optional[int] = None,
    deadline_s: Optional[float] = None,
    extra_env: Optional[Dict[str, str]] = None,
) -> Dict[str, Any]:
    """Run a fleet of replica-group subprocesses to ``target_steps`` on the
    anchor (replica 0, never killed); if ``kill_every`` > 0, SIGKILL a
    rotating victim among replicas 1..N-1 every ``kill_every`` anchor steps
    (waiting for the previous victim to rejoin first, so each heal-in is
    well defined).  Returns throughput + heal stats from the per-replica
    event logs."""
    from torchft_tpu import tier as tier_mod
    from torchft_tpu.launcher import ReplicaSpec, ReplicaSupervisor

    events_dir = tempfile.mkdtemp(prefix=f"tpuft_bench_{label}_")
    tier = tier_mod.default_tier()
    lighthouse = tier_mod.make_lighthouse(
        bind="127.0.0.1:0",
        min_replicas=1,
        # the join window is pure heal-in latency for a rejoining victim
        # (its quorum RPC parks for the full window when membership grows);
        # 1 s is ample straggler slack for localhost RPC while keeping the
        # standby-promotion heal in the join+transfer regime
        join_timeout_ms=int(os.environ.get("TPUFT_BENCH_JOIN_MS", "1000")),
        quorum_tick_ms=50,
        tier=tier,
    )
    env = {
        "TPUFT_BENCH_EVENTS_DIR": events_dir,
        "TPUFT_BENCH_TARGET_STEPS": str(target_steps),
        "TPUFT_BENCH_WORKER_PLATFORM": worker_platform or "",
        "TPUFT_BENCH_MODE": mode,
        "TPUFT_BENCH_DILOCO_SYNC": str(sizes["diloco_sync_every"]),
        "TPUFT_BENCH_DILOCO_FRAGMENTS": str(sizes["diloco_fragments"]),
        "TPUFT_BENCH_DILOCO_DELAY": str(sizes["diloco_sync_delay"]),
    }
    for k in ("dim", "layers", "seq", "batch", "head_dim"):
        env[f"TPUFT_BENCH_{k.upper()}"] = str(sizes[f"fleet_{k}"])
    if extra_env:
        env.update(extra_env)
    standby = bool(sizes.get("standby")) and kill_every > 0
    # with >= 3 replicas, leave the LAST victim cold (no spare): victim
    # rotation then produces both heal paths in one artifact, so the
    # standby-vs-cold heal-in comparison is measured, not assumed
    all_standby = os.environ.get("TPUFT_BENCH_ALL_STANDBY", "") not in ("", "0")
    cold_victim = (
        replicas - 1 if standby and replicas > 2 and not all_standby else None
    )
    specs = [
        ReplicaSpec(
            replica_group_id=i,
            cmd=[sys.executable, os.path.abspath(__file__), "--worker"],
            env=dict(env),
            # spares only behind killable replicas (0 is the anchor)
            standby=standby and i != 0 and i != cold_victim,
        )
        for i in range(replicas)
    ]
    supervisor = ReplicaSupervisor(
        specs,
        f"127.0.0.1:{lighthouse.port}",
        restart_delay_s=0.5,
    )
    runner = threading.Thread(target=supervisor.run, daemon=True)
    runner.start()

    # fragment-sync window start, in inner-cycle position (phase D kills
    # must land while the pseudogradient allreduce is in flight)
    per_frag = sizes["diloco_sync_every"] // sizes["diloco_fragments"]
    sync_cyc = per_frag - sizes["diloco_sync_delay"]

    kills: List[Dict[str, Any]] = []
    next_kill = kill_every
    victim = 1 if replicas > 1 else 0
    if deadline_s is None:
        deadline_s = 240.0 + 3.0 * target_steps + 90.0 * (
            (target_steps // kill_every) if kill_every else 0
        )
    deadline = time.time() + deadline_s
    heal_grace_s = 120.0
    stop_path = os.path.join(events_dir, "stop")
    try:
        while time.time() < deadline:
            anchor = _steps_of(_read_records(events_dir, 0))
            # gate on the PREVIOUS kill's victim having rejoined (committed
            # a step since its kill) — with rotation the next victim is a
            # different, healthy replica, and killing it while the last one
            # is still healing would overlap heals and corrupt attribution
            victim_back = bool(_steps_of(_read_records(events_dir, victim)))
            if kills:
                prev = _steps_of(_read_records(events_dir, kills[-1]["victim"]))
                victim_back = (
                    victim_back and bool(prev) and prev[-1][1] > kills[-1]["ts"]
                )
            if anchor and anchor[-1][0] >= target_steps:
                # anchor hit the measurement target; linger (bounded) so a
                # mid-heal victim gets to rejoin — that rejoin is the
                # heal-in data point
                if (
                    not kills
                    or victim_back
                    or time.time() - kills[-1]["ts"] > heal_grace_s
                ):
                    break
            elif (
                kill_every
                and anchor
                and anchor[-1][0] >= next_kill
                and victim_back
                and (max_kills is None or len(kills) < max_kills)
            ):
                if kill_in_sync_window:
                    # only pull the trigger while the victim reports being
                    # inside the fragment-sync window
                    cyc = next(
                        (
                            r.get("cyc")
                            for r in reversed(_read_records(events_dir, victim))
                            if "step" in r
                        ),
                        None,
                    )
                    if cyc is None or cyc < sync_cyc:
                        time.sleep(0.1)
                        continue
                if supervisor.kill(victim):
                    kills.append(
                        {
                            "ts": time.time(),
                            "survivor_step": anchor[-1][0],
                            "victim": victim,
                        }
                    )
                    print(
                        f"bench[{label}]: killed replica {victim} at anchor "
                        f"step {anchor[-1][0]}",
                        file=sys.stderr,
                    )
                    next_kill = anchor[-1][0] + kill_every
                    # rotate the victim among 1..N-1
                    if replicas > 2:
                        victim = 1 + (victim % (replicas - 1))
            time.sleep(0.25)
    finally:
        with open(stop_path, "w") as f:
            f.write("stop")
        runner.join(timeout=60)
        supervisor.stop()
        lighthouse.shutdown()

    records = [_read_records(events_dir, i) for i in range(replicas)]
    return _fleet_metrics(label, target_steps, records, kills)


def _fleet_metrics(
    label: str,
    target_steps: int,
    records: List[List[Dict[str, Any]]],
    kills: List[Dict[str, Any]],
) -> Dict[str, Any]:
    """Throughput + heal statistics from the committed-step event logs.

    All replica processes share one physical chip in this harness, so
    survivors literally speed up while a peer is dead (decontention) — a
    raw with-faults/fault-free wall-clock ratio would overstate fault
    tolerance.  Instead the fault cost is measured directly: the anchor's
    steady-state step time during all-alive periods (``t_step_s``) vs the
    extra time its disrupted steps took around each kill and each rejoin
    (``overhead_per_kill_s``).  BASELINE's fault rate is one kill per 100
    steps, so the north-star ratio is ``100·t / (100·t + overhead)``.
    """
    evs = [_steps_of(r) for r in records]
    anchor = evs[0]
    result: Dict[str, Any] = {
        "label": label,
        "replicas": len(records),
        "kills": len(kills),
        "anchor_steps": anchor[-1][0] if anchor else 0,
        "completed": bool(anchor and anchor[-1][0] >= target_steps),
    }
    if len(anchor) < 2:
        return result

    # per-step durations for the anchor: dts[i] = time to commit anchor[i]
    dts = [
        (anchor[i][0], anchor[i][1], anchor[i][1] - anchor[i - 1][1])
        for i in range(1, len(anchor))
    ]

    def _outstanding(ts: float) -> bool:
        """True when some kill before ``ts`` has no victim rejoin yet."""
        for kill in kills:
            if kill["ts"] > ts:
                continue
            vic = evs[kill["victim"]]
            if not any(kill["ts"] < t <= ts for (_s, t) in vic):
                return True
        return False

    steady = [dt for (_s, ts, dt) in dts if not _outstanding(ts)]
    if steady:
        steady_sorted = sorted(steady)
        t_step = steady_sorted[len(steady_sorted) // 2]
        result["t_step_s"] = round(t_step, 4)
        result["anchor_steps_per_sec"] = round(1.0 / t_step, 3)
    else:
        t_step = None

    # wall-clock throughput over the whole phase (raw, contention-skewed)
    span_steps = anchor[-1][0] - anchor[0][0]
    span_time = anchor[-1][1] - anchor[0][1]
    if span_steps > 0 and span_time > 0:
        result["anchor_steps_per_sec_raw"] = round(span_steps / span_time, 3)

    # DiLoCo: cost of a fragment sync = median sync-step time minus median
    # plain-inner-step time (how well the τ-delayed allreduce overlaps)
    anchor_steps_recs = [r for r in records[0] if "step" in r]
    if t_step is not None and any(r.get("sync") for r in anchor_steps_recs):
        by_ts = {r["ts"]: bool(r.get("sync")) for r in anchor_steps_recs}
        sync_dts = sorted(
            dt for (_s, ts, dt) in dts
            if by_ts.get(ts) and not _outstanding(ts)
        )
        plain_dts = sorted(
            dt for (_s, ts, dt) in dts
            if not by_ts.get(ts) and not _outstanding(ts)
        )
        if sync_dts and plain_dts:
            sync_t = sync_dts[len(sync_dts) // 2]
            plain_t = plain_dts[len(plain_dts) // 2]
            result["sync_step_s"] = round(sync_t, 4)
            result["inner_step_s"] = round(plain_t, 4)
            result["sync_overhead_s"] = round(max(0.0, sync_t - plain_t), 4)

    # per-kill disruption + heal attribution
    heal_secs: List[float] = []
    heal_steps: List[int] = []
    overheads: List[float] = []
    breakdowns: List[Dict[str, float]] = []
    by_victim: Dict[int, List[float]] = {}
    for kill in kills:
        # the rejoin record (first committed step after the kill) — read it
        # once so ts and the rejoining incarnation's pid come from the same
        # event (matching them up later by float ts equality would be
        # fragile)
        rejoin_rec = next(
            (
                r
                for r in records[kill["victim"]]
                if "step" in r and r["ts"] > kill["ts"]
            ),
            None,
        )
        rejoin_ts = rejoin_rec["ts"] if rejoin_rec else None
        if rejoin_ts is not None:
            heal_secs.append(rejoin_ts - kill["ts"])
            by_victim.setdefault(kill["victim"], []).append(
                rejoin_ts - kill["ts"]
            )
            anchor_at_rejoin = max(
                (s for (s, t) in anchor if t <= rejoin_ts),
                default=kill["survivor_step"],
            )
            heal_steps.append(
                max(0, anchor_at_rejoin - kill["survivor_step"])
            )
            bd = _heal_breakdown(
                records[kill["victim"]],
                kill["ts"],
                rejoin_ts,
                rejoin_rec.get("pid"),
            )
            if bd:
                breakdowns.append(bd)
        if t_step is not None:
            if rejoin_ts is not None:
                window_end = rejoin_ts + 3 * t_step
            else:
                window_end = kill["ts"] + 10 * t_step
            dis = [
                dt for (_s, ts, dt) in dts if kill["ts"] <= ts <= window_end
            ]
            overheads.append(sum(max(0.0, dt - t_step) for dt in dis))
    if heal_secs:
        # seconds is the environment-independent number (process respawn +
        # jax init + rejoin + heal transfer); steps would scale with the
        # survivor's decontended step time and mislead
        result["mean_heal_in_s"] = round(sum(heal_secs) / len(heal_secs), 1)
        result["heal_in_s"] = [round(h, 1) for h in heal_secs]
        result["heal_in_steps"] = heal_steps
        result["heal_by_victim"] = {
            str(v): [round(h, 1) for h in hs] for v, hs in by_victim.items()
        }
    if breakdowns:
        numeric_keys = sorted(
            {
                k
                for bd in breakdowns
                for k, v in bd.items()
                if isinstance(v, (int, float)) and not isinstance(v, bool)
            }
        )
        agg: Dict[str, Any] = {
            # mean over the kills in which the phase occurred (a key absent
            # from a breakdown means that heal path skipped the phase, not
            # that it took 0 s — cold respawns have no promote_s and
            # standby promotions have no respawn_s)
            k: round(
                sum(bd[k] for bd in breakdowns if k in bd)
                / sum(1 for bd in breakdowns if k in bd),
                2,
            )
            for k in numeric_keys
        }
        agg["paths"] = {
            p: sum(1 for bd in breakdowns if bd.get("path") == p)
            for p in {bd.get("path") for bd in breakdowns}
        }
        agg["all_sane"] = all(bd.get("sane") for bd in breakdowns)
        result["heal_breakdown"] = agg
        result["heal_breakdowns"] = breakdowns
        # mean heal-in per path: the warm-standby payoff (vs cold respawn)
        # measured head-to-head in one artifact.  breakdowns[i] and
        # heal_secs[i] describe the same rejoin (appended together above)
        if len(breakdowns) == len(heal_secs):
            by_path: Dict[str, List[float]] = {}
            for bd, h in zip(breakdowns, heal_secs):
                by_path.setdefault(bd["path"], []).append(h)
            result["heal_in_s_by_path"] = {
                p: round(sum(hs) / len(hs), 1) for p, hs in by_path.items()
            }
    if overheads:
        result["overhead_per_kill_s"] = round(
            sum(overheads) / len(overheads), 3
        )
        if t_step:
            per100 = 100.0 * t_step
            result["ratio_per_100step_kill"] = round(
                per100 / (per100 + result["overhead_per_kill_s"]), 4
            )
    return result


def _heal_breakdown(
    victim_records: List[Dict[str, Any]],
    kill_ts: float,
    rejoin_ts: float,
    rejoin_pid: Optional[int],
) -> Dict[str, Any]:
    """Attribute one victim rejoin to phases, from its phase log:
    respawn (supervisor delay + python boot), jax_init (backend
    start), model_build (init + device_put + trace), promote (death
    detection + gate release, warm-standby path), manager (ctor + server
    + store), join_to_first_commit (quorum rpc incl. join window,
    rendezvous, checkpoint transfer — sub-attributed from Manager timings,
    plus first-step compile).

    Only the **rejoining incarnation's** phases count (matched by pid): the
    group's log interleaves the killed process, the promoted standby, and
    the fresh spare re-warmed behind it — the spare's boot phases land
    inside the kill→rejoin window but are off the heal path (round-3
    artifact had ``promote_s = -5.44`` from exactly this mixing)."""
    phases = [
        p
        for p in _phases_of(victim_records)
        if kill_ts < p["ts"] <= rejoin_ts
        and (rejoin_pid is None or p.get("pid") == rejoin_pid)
    ]
    t = {p["phase"]: p for p in phases}
    out: Dict[str, Any] = {}
    prev = kill_ts
    for name, key in (
        ("proc_start", "respawn_s"),
        ("jax_ready", "jax_init_s"),
        ("model_ready", "model_build_s"),
        # warm-standby takeover: the phases above are absent — the spare
        # paid them before the kill, while parked
        ("standby_promoted", "promote_s"),
        ("manager_ready", "manager_s"),
        # sub-attribution of the join window (ddp workers log these ONCE,
        # on their first loop iteration): loop entry, first grads computed
        # (compile + compute), quorum ready (join + configure + heal
        # transfer).  Best-effort: when the first quorum funnels an error
        # and the real heal happens on iteration 2+, the later work lands
        # in the residual below — visible as a large join_to_first_commit_s
        # rather than misattributed to a named phase
        ("first_started", "first_loop_s"),
        ("first_grads_ready", "first_grads_s"),
        ("first_quorum_ready", "quorum_wait_s"),
    ):
        if name in t:
            out[key] = t[name]["ts"] - prev
            prev = t[name]["ts"]
    # residual after the last logged phase: allreduce wire + should_commit
    # barrier + weight update (small once the sub-phases above exist)
    out["join_to_first_commit_s"] = rejoin_ts - prev
    # trust signal: every phase must be non-negative (the walk chains
    # timestamps of ONE process, so a negative means cross-incarnation
    # mixing), and the rejoiner must have logged manager_ready — it cannot
    # have committed a step without constructing a Manager, so its absence
    # means the pid filter matched the wrong (or no) incarnation
    numeric = [v for v in out.values() if isinstance(v, float)]
    out["path"] = "standby" if "standby_promoted" in t else "cold"
    out["sane"] = bool(
        all(v >= -1e-6 for v in numeric) and "manager_ready" in t
    )
    fc = t.get("first_commit")
    if fc and isinstance(fc.get("timings"), dict):
        for k, v in fc["timings"].items():
            out[f"quorum_{k}"] = v
    return {
        k: (round(v, 3) if isinstance(v, float) else v)
        for k, v in out.items()
    }


# --------------------------------------------------------------------------
# phase A: single-chip ws=1 overhead + absolute tokens/sec/chip + MFU
# --------------------------------------------------------------------------


def run_single(sizes: Dict[str, int]) -> Dict[str, Any]:
    """Phase A with remat-mode walk: cheaper-recompute modes are tried
    first and an activation OOM falls back toward full per-layer remat."""
    modes = _phase_a_modes(sizes)
    last_err: Optional[BaseException] = None
    for i, mode in enumerate(modes):
        try:
            return _run_single_mode(sizes, mode)
        except Exception as e:  # noqa: BLE001 — inspect for OOM class
            msg = str(e)
            oom = (
                "RESOURCE_EXHAUSTED" in msg
                or "Out of memory" in msg
                or "out of memory" in msg
                or isinstance(e, MemoryError)
            )
            if oom and i + 1 < len(modes):
                print(
                    f"bench: phase A remat mode {mode!r} OOM; retrying "
                    f"with {modes[i + 1]!r}",
                    file=sys.stderr,
                )
                # drop the traceback: it pins the failed attempt's frame —
                # and with it the params/opt buffers in HBM — which would
                # make the fallback mode OOM too
                last_err = e.with_traceback(None)
                continue
            raise
    raise last_err  # pragma: no cover - loop always returns or raises


def _run_single_mode(sizes: Dict[str, int], remat_mode: str) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp
    import optax

    from torchft_tpu import tier as tier_mod
    from torchft_tpu.ddp import ft_allreduce
    from torchft_tpu.manager import Manager
    from torchft_tpu.optim import OptimizerWrapper

    steps = sizes["steps"]
    model, config = _build_model(sizes, remat_mode=remat_mode)
    device = jax.devices()[0]
    flash = model._use_flash(sizes["seq"])
    print(
        f"bench: llama dim={config.dim} layers={config.n_layers} "
        f"seq={sizes['seq']} batch={sizes['batch']} "
        f"heads={config.n_heads}x{config.head_dim} "
        f"params={model.num_params()/1e6:.1f}M remat={remat_mode} "
        f"flash={flash} on {device.platform} ({device.device_kind})",
        file=sys.stderr,
    )

    params = jax.device_put(model.init(jax.random.PRNGKey(0)), device)
    tx = optax.adamw(1e-3)
    key = jax.random.PRNGKey(1)
    tokens = jax.random.randint(
        key, (sizes["batch"], sizes["seq"]), 0, config.vocab_size
    )
    targets = jnp.roll(tokens, -1, axis=1)
    batch_data = (jax.device_put(tokens, device), jax.device_put(targets, device))
    tokens_per_step = sizes["batch"] * sizes["seq"]

    grad_step = jax.jit(jax.value_and_grad(model.loss))

    # fault-free baseline: ALL measured steps inside ONE jitted lax.scan
    # (one dispatch, data-dependent carry so XLA can't elide work, one sync
    # at the end); MFU is computed from it.  The FT path below stays a
    # per-step python loop — its protocol work is host-side by design — so
    # ``ws1_ratio`` includes per-step dispatch overhead (ROADMAP S1 replaces
    # this unlike-for-unlike comparison).
    def train_scan(p, o):
        def body(carry, _):
            p, o = carry
            loss, grads = jax.value_and_grad(model.loss)(p, batch_data)
            updates, o = tx.update(grads, o, p)
            p = optax.apply_updates(p, updates)
            return (p, o), loss

        (p, o), losses = jax.lax.scan(body, (p, o), None, length=steps)
        return p, o, losses

    # deep copy: the scan donates its inputs, and the FT phase below must
    # not read donated buffers
    ff_params = jax.tree_util.tree_map(jnp.copy, params)
    opt_state = jax.jit(tx.init)(ff_params)
    scan_compiled = (
        jax.jit(train_scan, donate_argnums=(0, 1))
        .lower(ff_params, opt_state)
        .compile()
    )
    # one short warmup dispatch before timing
    loss0, grads0 = grad_step(params, batch_data)
    jax.block_until_ready(loss0)
    del loss0, grads0

    start = time.perf_counter()
    ff_params, opt_state, losses = scan_compiled(ff_params, opt_state)
    jax.block_until_ready(losses)
    faultfree_s = (time.perf_counter() - start) / steps
    faultfree_tps = tokens_per_step / faultfree_s
    print(
        f"fault-free (scan x{steps}): {faultfree_s*1e3:.1f} ms/step, "
        f"{faultfree_tps:,.0f} tok/s",
        file=sys.stderr,
    )
    # free the baseline's params+optimizer copies BEFORE the FT stack
    # allocates its own — at ~1B params two live copies OOM a single chip
    del ff_params, opt_state, losses, scan_compiled
    jax.block_until_ready(params)

    # full FT stack, ws=1, on the production tier
    tier = tier_mod.default_tier()
    lighthouse = tier_mod.make_lighthouse(
        bind="127.0.0.1:0",
        min_replicas=1,
        join_timeout_ms=50,
        quorum_tick_ms=20,
        tier=tier,
    )
    holder = {"params": params, "opt_state": jax.jit(tx.init)(params)}
    manager = None
    try:
        manager = Manager(
            comm=tier_mod.make_communicator(timeout_s=60.0),
            load_state_dict=lambda s: holder.update(s),
            state_dict=lambda: dict(holder),
            min_replica_size=1,
            replica_id="bench_0",
            lighthouse_addr=lighthouse.local_address(),
            server_cls=tier_mod.manager_server_cls(tier),
        )
        opt = OptimizerWrapper(manager, tx)

        def ft_step() -> None:
            opt.start_step()
            loss, grads = grad_step(holder["params"], batch_data)
            grads = ft_allreduce(manager, grads)
            opt.step(holder, grads)

        for _ in range(4):  # warm the protocol path + post-compile iterations
            ft_step()
        jax.block_until_ready(holder["params"])

        start = time.perf_counter()
        for _ in range(steps):
            ft_step()
        jax.block_until_ready(holder["params"])
        ft_s = (time.perf_counter() - start) / steps
        ft_tps = tokens_per_step / ft_s
        print(
            f"ft: {ft_s*1e3:.1f} ms/step, {ft_tps:,.0f} tok/s", file=sys.stderr
        )
    finally:
        # shutdown on EVERY path: an OOM here sends run_single to the next
        # remat mode, and a leaked Manager's state_dict closure would pin
        # holder's params + opt_state in HBM (and stack live servers per
        # retry)
        if manager is not None:
            manager.shutdown()
        lighthouse.shutdown()

    # achieved model FLOPs: the standard 6N per token for the train step
    # (fwd+bwd) plus the attention score/value matmuls 12·L·dim·S.  N
    # excludes the embedding table (a gather, not a matmul — PaLM MFU
    # convention) but keeps the lm_head projection, which is a real matmul
    matmul_params = model.num_params() - config.vocab_size * config.dim
    flops_per_token = (
        6 * matmul_params + 12 * config.n_layers * config.dim * sizes["seq"]
    )
    # MFU from the scanned fault-free loop (the compute stack's ceiling —
    # one dispatch, no host protocol); the FT path's throughput and its
    # own MFU are reported alongside so the protocol + dispatch tax is
    # visible rather than folded into the headline
    tflops = faultfree_tps * flops_per_token / 1e12
    ft_tflops = ft_tps * flops_per_token / 1e12
    out = {
        "faultfree_tokens_per_sec": round(faultfree_tps, 1),
        "ft_tokens_per_sec": round(ft_tps, 1),
        "ws1_ratio": round(ft_tps / faultfree_tps, 4),
        "model_tflops_per_sec": round(tflops, 2),
        "ft_model_tflops_per_sec": round(ft_tflops, 2),
        "platform": device.platform,
        "device_kind": device.device_kind,
        "tier": tier,
        "remat": remat_mode,
        "flash": bool(flash),
    }
    peak = _peak_tflops(device)
    if peak:
        out["peak_tflops"] = peak
        out["mfu"] = round(tflops / peak, 4)
        out["mfu_ft"] = round(ft_tflops / peak, 4)
        factor = _REMAT_HW_FACTOR.get(remat_mode, 1.0)
        if factor > 1.0:
            # remat re-runs part of the forward in the backward: hardware
            # does ~factor*6N/token against the 6N the MFU convention counts
            out["hw_mfu_est"] = round(tflops * factor / peak, 4)
    print(
        f"bench: {tflops:.2f} model TFLOP/s (scan), {ft_tflops:.2f} (ft), "
        f"mfu={out.get('mfu')} mfu_ft={out.get('mfu_ft')}",
        file=sys.stderr,
    )
    return out


def _headline_heal_keys(faults: Dict[str, Any]) -> Dict[str, Any]:
    """Lift the aggregated ``heal_breakdown`` phases into top-level
    headline keys (respawn / join / transfer / first-commit, plus the
    standby promote phase) so the spare-promotion gate is comparable
    round-over-round without digging into bench_out.json.  A key is None
    when no kill exercised that phase this round (cold respawns have no
    promote_s, standby promotions no respawn_s)."""
    bd = faults.get("heal_breakdown") or {}
    return {
        "heal_respawn_s": bd.get("respawn_s"),
        "heal_join_s": bd.get("quorum_wait_s"),
        "heal_transfer_s": bd.get("quorum_heal_recv_s"),
        "heal_first_commit_s": bd.get("join_to_first_commit_s"),
        "heal_promote_s": bd.get("promote_s"),
    }


def _run_spare_phase(num_replicas: int = 3, steps: int = 10) -> Dict[str, Any]:
    """Hot-spare promotion gate: the thread-plane spare drill (3 actives +
    1 continuously-warmed spare, one active killed) under the ``wan_1g``
    profile.  Reports ``mean_heal_in_s`` via promotion, to sit side by
    side with the process fleet's cold/standby heal-in — the PR-6 payoff
    (<1 s vs 6–12 s) measured in one artifact."""
    from torchft_tpu.drill import gray_failure_drill

    saved = {k: os.environ.get(k) for k in ("TORCHFT_NET_EMU",)}
    os.environ["TORCHFT_NET_EMU"] = "wan_1g"
    try:
        report = gray_failure_drill(
            mode="spare_promote", num_replicas=num_replicas, steps=steps
        )
        return {
            "profile": "wan_1g",
            "replicas": num_replicas,
            "spares": 1,
            "mean_heal_in_s": report["mean_heal_in_s"],
            "promotion_latency_s": report["promotion_latency_s"],
            "warm_lag_steps": report["warm_lag_steps"],
            "quorum_reconfigs": report["quorum_reconfigs"],
            "promotions_total": report["promotions_total"],
        }
    except Exception as e:  # noqa: BLE001 — a failed drill is a recorded
        # fact, never a lost artifact
        return {"error": f"{type(e).__name__}: {e}"}
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def _run_degraded_phase(num_replicas: int = 3, steps: int = 10) -> Dict[str, Any]:
    """Degraded-mode gate (ISSUE 13): two thread-plane drills under the
    ``wan_1g`` profile — (a) an in-replica device loss absorbed in place
    (``degraded_step_time_ratio``: wounded-fleet step time vs the pre-wound
    baseline, zero membership edits), and (b) the same wound with a warm
    full-width spare registered (``wound_to_swap_s``: wound detection →
    spare swapped in as ONE membership edit)."""
    from torchft_tpu.drill import gray_failure_drill

    saved = {k: os.environ.get(k) for k in ("TORCHFT_NET_EMU",)}
    os.environ["TORCHFT_NET_EMU"] = "wan_1g"
    out: Dict[str, Any] = {"profile": "wan_1g", "replicas": num_replicas}
    try:
        try:
            wound = gray_failure_drill(
                mode="device_loss", num_replicas=num_replicas, steps=steps
            )
            out.update(
                degraded_step_time_ratio=wound.get("degraded_step_time_ratio"),
                capacity_observed=wound.get("capacity_observed"),
                wound_quorum_reconfigs=wound.get("quorum_reconfigs"),
                converged=wound.get("converged"),
            )
        except Exception as e:  # noqa: BLE001 — a failed drill is a
            # recorded fact, never a lost artifact
            out["device_loss_error"] = f"{type(e).__name__}: {e}"
        try:
            swap = gray_failure_drill(
                mode="device_loss_swap",
                num_replicas=num_replicas,
                steps=steps,
            )
            out.update(
                wound_to_swap_s=swap.get("wound_to_swap_s"),
                swaps_total=swap.get("swaps_total"),
                swap_quorum_reconfigs=swap.get("quorum_reconfigs"),
            )
        except Exception as e:  # noqa: BLE001
            out["swap_error"] = f"{type(e).__name__}: {e}"
        return out
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def _run_coord_phase(num_replicas: int) -> Dict[str, Any]:
    """Coordination-plane scale gate (ISSUE 12): the thread-plane harness
    drives ``num_replicas`` simulated replicas + a spare pool through
    quorum/kill/rejoin/promote churn and an aggregator bounce against a
    subprocess lighthouse, reporting p99 quorum latency, lighthouse CPU,
    and the lighthouse-inbound beat-RPC reduction vs direct heartbeats.
    Pure control plane — no accelerator, no data plane — so it costs tens
    of seconds regardless of platform."""
    from torchft_tpu.coord.scale import run_scale_harness

    try:
        return run_scale_harness(
            num_replicas=num_replicas,
            num_aggregators=2,
            num_spares=2,
            kills=1,
            rejoins=1,
            agg_bounce=True,
            deadline_s=150.0,
        )
    except Exception as e:  # noqa: BLE001 — a failed phase is a recorded
        # fact, never a lost artifact
        return {"error": f"{type(e).__name__}: {e}"}


def _run_obs_phase() -> Dict[str, Any]:
    """Observability-overhead gate (ISSUE 14): the flight recorder + trace
    spans must cost <= 1% of step time when fully enabled.

    Two measurements, combined as a ratio:

    - **step time**: a synthetic step (a fixed numpy matmul workload sized
      to a few milliseconds — conservative: a real train step is orders of
      magnitude longer, making the same absolute obs cost proportionally
      smaller), median over ``TPUFT_BENCH_OBS_STEPS``.
    - **obs cost per step**: the per-step event/span pattern the real
      protocol emits (~14 events + ~8 spans: quorum start/adopt, lane
      windows, vote/commit) run WITHOUT the workload, thousands of
      repetitions, enabled minus disabled — the marginal cost of turning
      recorder + spans on, measured to sub-microsecond resolution instead
      of differencing two multi-millisecond legs whose ambient jitter
      would swamp a <1% effect.

    ``obs_overhead_frac = obs_cost_per_step / step_time``."""
    import numpy as np

    from torchft_tpu.obs import spans as obs_spans
    from torchft_tpu.obs.flight import FlightEvent, FlightRecorder

    steps = int(os.environ.get("TPUFT_BENCH_OBS_STEPS", "") or 40)
    rng = np.random.default_rng(3)
    a = rng.normal(size=(896, 896)).astype(np.float32)
    b = rng.normal(size=(896, 896)).astype(np.float32)

    def obs_pattern(rec: FlightRecorder, i: int) -> None:
        span = obs_spans.span
        rec.set_context(step=i, quorum_id=1)
        rec.record(FlightEvent.QUORUM_START, step=i)
        with span("tpuft/manager/quorum", step=i):
            rec.record(FlightEvent.QUORUM_ADOPT, step=i, world=3)
        with span("tpuft/comm/op", epoch=1):
            for lane in range(4):
                with span("tpuft/comm/lane_window", lane=lane):
                    rec.record(
                        FlightEvent.COMM_CONFIGURE, rank=0, world=3, lanes=4
                    )
        with span("tpuft/manager/fence", step=i):
            rec.record(FlightEvent.COMMIT_FENCE, step=i)
        for _ in range(6):  # heal/lane/chaos-shaped background events
            rec.record(FlightEvent.LANE_RECONNECT, peer=1, lane=0)
        rec.record(FlightEvent.COMMIT_VOTE, step=i, local=True)
        with span("tpuft/manager/should_commit", step=i):
            rec.record(FlightEvent.COMMIT_RESULT, step=i, committed=True)

    def measure_pattern(rec: FlightRecorder, spans_on: bool, reps: int) -> float:
        obs_spans.configure(spans_on)
        for i in range(50):  # warm caches + the allocator
            obs_pattern(rec, i)
        t0 = time.perf_counter()
        for i in range(reps):
            obs_pattern(rec, i)
        return (time.perf_counter() - t0) / reps

    saved_enabled = obs_spans._enabled
    try:
        # the step the tax is measured against (median beats jitter)
        times = []
        for _ in range(max(8, steps)):
            t0 = time.perf_counter()
            _ = a @ b
            _ = a @ b
            times.append(time.perf_counter() - t0)
        t_step = float(np.median(times))

        off_rec = FlightRecorder("bench_obs_off", cap=0)
        on_rec = FlightRecorder("bench_obs_on", cap=4096)
        reps = 2000
        t_pat_off = measure_pattern(off_rec, spans_on=False, reps=reps)
        t_pat_on = measure_pattern(on_rec, spans_on=True, reps=reps)
        obs_cost = max(0.0, t_pat_on - t_pat_off)
        frac = obs_cost / t_step if t_step > 0 else 0.0
        return {
            "obs_overhead_frac": round(frac, 5),
            "step_ms": round(t_step * 1e3, 4),
            "obs_cost_us_per_step": round(obs_cost * 1e6, 3),
            "pattern_us_disabled": round(t_pat_off * 1e6, 3),
            "pattern_us_enabled": round(t_pat_on * 1e6, 3),
            "events_per_step": 14,
            "spans_per_step": 8,
            "events_recorded": len(on_rec),
            "spans_recorded": len(obs_spans.snapshot()),
        }
    finally:
        obs_spans.configure(saved_enabled)
        obs_spans.clear()


_PARTIAL: Dict[str, Any] = {}
_PARTIAL_PATH = os.environ.get(
    "TPUFT_BENCH_OUT", os.path.join(REPO, "bench_out.json")
)


def _emit_partial(**updates: Any) -> None:
    """Stream results to ``bench_out.json`` as each phase completes, so a
    driver that captures only the output tail — or a late-phase hang — can
    never lose the already-measured numbers (round 3 lost the MFU head to
    exactly that truncation)."""
    _PARTIAL.update(updates)
    _PARTIAL["partial_ts"] = round(time.time(), 1)
    try:
        tmp = _PARTIAL_PATH + ".tmp"
        with open(tmp, "w") as f:
            json.dump(_PARTIAL, f, indent=1)
        os.replace(tmp, _PARTIAL_PATH)
    except OSError as e:  # a broken sink must not kill the bench
        print(f"bench: cannot write {_PARTIAL_PATH}: {e}", file=sys.stderr)


def _install_hard_deadline(deadline_ts: float) -> None:
    """Last-resort watchdog for the driver's external ``timeout`` wrapper.

    The soft budget checks run BETWEEN phases, so one overrunning phase
    (round 5: the DiLoCo sweep on a slow CPU) can sail past the budget and
    let the external ``timeout`` SIGKILL the bench — rc=124, no final JSON
    line, the whole round lost (BENCH_r05 recorded exactly that: ``parsed:
    null`` with every per-scenario number already measured).  At
    ``deadline_ts`` this thread flushes the partial per-scenario artifact,
    prints a complete headline JSON line assembled from whatever phases
    finished, and exits 0 — a truncated-but-parseable round beats a dead
    one.  ``os._exit`` on purpose: the wedged phase may be blocked in
    uninterruptible jax/socket calls that a SystemExit would never unwind.
    """
    import threading

    def _fire() -> None:
        _emit_partial(deadline_expired=True)
        single = _PARTIAL.get("single") or {}
        headline = {
            "metric": "ft_vs_faultfree_tokens_per_sec_ratio",
            "value": single.get("ws1_ratio"),
            "unit": "ratio",
            "platform": single.get("platform"),
            "tier": single.get("tier"),
            "mfu": single.get("mfu"),
            # coordination headline keys land even on a watchdog trip —
            # they streamed into _PARTIAL the moment the phase finished
            "coord_p99_quorum_latency_s": _PARTIAL.get(
                "coord_p99_quorum_latency_s"
            ),
            "lighthouse_cpu_frac": _PARTIAL.get("lighthouse_cpu_frac"),
            "deadline_expired": True,
            "phases_done": sorted(
                k for k in _PARTIAL if k not in ("partial_ts", "final")
            ),
            "detail": "bench_out.json",
        }
        print(
            "bench: HARD DEADLINE expired — emitting partial artifact and "
            "exiting 0 (see bench_out.json for completed phases)",
            file=sys.stderr,
        )
        print(json.dumps(headline), flush=True)
        sys.stderr.flush()
        os._exit(0)

    timer = threading.Timer(max(0.0, deadline_ts - time.time()), _fire)
    timer.daemon = True
    timer.start()


def main() -> None:
    # total wall-clock budget: a driver that kills a long bench would
    # capture NO final JSON line at all, so the bench bounds itself and
    # prints whatever phases completed (the streaming bench_out.json plus
    # this guarantee = an artifact on every path)
    budget_s = float(os.environ.get("TPUFT_BENCH_TOTAL_BUDGET_S", "2100"))
    t_start = time.time()
    # hard self-deadline (one straddling phase floor + teardown of margin):
    # MUST fire before any external `timeout` wrapper so the round always
    # ends with a parseable artifact + headline instead of rc=124
    hard_deadline_s = float(
        os.environ.get("TPUFT_BENCH_HARD_DEADLINE_S", "") or budget_s + 420.0
    )
    if hard_deadline_s > 0:
        _install_hard_deadline(t_start + hard_deadline_s)

    def remaining_s() -> float:
        return budget_s - (time.time() - t_start)

    _configure_jax(os.environ.get("TPUFT_BENCH_PLATFORM"))

    import jax

    backend = jax.default_backend()
    on_cpu = backend == "cpu"
    if on_cpu and os.environ.get("TPUFT_BENCH_PLATFORM") != "cpu":
        sys.exit(
            "bench: JAX found no TPU. A CPU run proves the protocol and "
            "counts, never a device number, and runs only when "
            "TPUFT_BENCH_PLATFORM=cpu asks for it."
        )
    skip_fleet = bool(os.environ.get("TPUFT_BENCH_SKIP_FLEET"))
    if backend == "tpu" and not skip_fleet:
        sys.exit(
            "bench: the fleet and DiLoCo phases start one worker process "
            "per replica plus warm standbys, and each would need a chip of "
            "its own; this process already holds it, so they cannot start. "
            "Set TPUFT_BENCH_SKIP_FLEET=1 for phase A and the thread-plane "
            "phases (ROADMAP S1 replaces the fleet with thread replicas)."
        )
    sizes = _sizes(on_cpu)
    _emit_partial(platform=backend, sizes={k: v for k, v in sizes.items()})

    single = run_single(sizes)
    _emit_partial(single=single)

    faults: Dict[str, Any] = {}
    diloco: Dict[str, Any] = {}
    ratio = None
    if not skip_fleet and remaining_s() < 60.0:
        # budget already exhausted (phase A ran long): skipping
        # beats stacking the 120/180 s fleet floors past the stated budget
        skip_fleet = True
        faults = {
            "note": (
                f"fleet phases skipped: total budget exhausted "
                f"({remaining_s():.0f}s left of {budget_s:.0f}s)"
            )
        }
    if not skip_fleet:
        fleet_deadline_ts = t_start + budget_s
        worker_platform = "cpu" if on_cpu else None
        replicas = max(2, sizes["replicas"])
        faultfree = run_fleet(
            "faultfree",
            target_steps=max(10, sizes["fleet_steps"] // 3),
            sizes=sizes,
            worker_platform=worker_platform,
            replicas=replicas,
            deadline_s=_budget_left(fleet_deadline_ts, 0.25, 120.0),
        )
        print(f"bench: fleet fault-free {faultfree}", file=sys.stderr)
        _emit_partial(faultfree_fleet=faultfree)
        faulted = run_fleet(
            "faults",
            target_steps=sizes["fleet_steps"],
            sizes=sizes,
            worker_platform=worker_platform,
            kill_every=sizes["kill_every"],
            replicas=replicas,
            deadline_s=_budget_left(fleet_deadline_ts, 0.55, 180.0),
        )
        print(f"bench: fleet with faults {faulted}", file=sys.stderr)
        _emit_partial(faulted_fleet=faulted)
        faults = {
            "fleet_steps": sizes["fleet_steps"],
            "kill_every": sizes["kill_every"],
            "replicas": replicas,
            "standby": bool(sizes.get("standby")),
            "kills": faulted.get("kills", 0),
            "faultfree_fleet": faultfree,
            "faulted_fleet": faulted,
        }
        for k in ("mean_heal_in_s", "heal_breakdown"):
            if faulted.get(k) is not None:
                faults[k] = faulted[k]
        ratio = faulted.get("ratio_per_100step_kill")

        if not os.environ.get("TPUFT_BENCH_SKIP_DILOCO"):
            if remaining_s() > 240.0:
                diloco = _run_diloco_phase(
                    sizes,
                    worker_platform,
                    replicas,
                    deadline_ts=t_start + budget_s,
                )
            else:
                diloco = {
                    "skipped": (
                        f"total budget exhausted ({remaining_s():.0f}s left "
                        f"of {budget_s:.0f}s); raise TPUFT_BENCH_TOTAL_BUDGET_S"
                    )
                }
            _emit_partial(diloco=diloco)

        if not os.environ.get("TPUFT_BENCH_SKIP_SPARE"):
            # hot-spare promotion gate (thread plane, wan_1g): cheap —
            # seconds, not minutes — so it only needs a token budget floor
            if remaining_s() > 30.0:
                spare_promotion = _run_spare_phase()
            else:
                spare_promotion = {
                    "skipped": f"budget exhausted ({remaining_s():.0f}s left)"
                }
            print(f"bench: spare promotion {spare_promotion}", file=sys.stderr)
            _emit_partial(spare_promotion=spare_promotion)
            faults["spare_promotion"] = spare_promotion

    if not os.environ.get("TPUFT_BENCH_SKIP_DEGRADED"):
        # degraded-mode gate (thread plane, wan_1g): independent of the
        # fleet phases (it drives its own drill fleet), so it runs — or
        # records why it didn't — even when the fleet block is skipped;
        # like the spare phase it costs seconds, so a token budget floor
        # suffices
        if remaining_s() > 30.0:
            degraded = _run_degraded_phase()
        else:
            degraded = {
                "skipped": f"budget exhausted ({remaining_s():.0f}s left)"
            }
        print(f"bench: degraded {degraded}", file=sys.stderr)
        # the two degraded headline keys stream as TOP-LEVEL partial
        # keys the moment the phase lands, so a watchdog trip still
        # reports them (the BENCH_r05 lesson)
        _emit_partial(
            degraded=degraded,
            degraded_step_time_ratio=degraded.get(
                "degraded_step_time_ratio"
            ),
            wound_to_swap_s=degraded.get("wound_to_swap_s"),
        )
        faults["degraded"] = degraded

    coord: Dict[str, Any] = {}
    if not os.environ.get("TPUFT_BENCH_SKIP_COORD"):
        if remaining_s() > 60.0:
            coord = _run_coord_phase(
                int(
                    os.environ.get("TPUFT_BENCH_COORD_REPLICAS", 0)
                    or (120 if on_cpu else 500)
                )
            )
        else:
            coord = {
                "skipped": f"budget exhausted ({remaining_s():.0f}s left)"
            }
        print(f"bench: coord {coord}", file=sys.stderr)
        # the two coordination headline keys stream as TOP-LEVEL partial
        # keys the moment the phase lands, so a watchdog trip still
        # reports them (the BENCH_r05 lesson)
        _emit_partial(
            coord=coord,
            coord_p99_quorum_latency_s=coord.get("p99_quorum_latency_s"),
            lighthouse_cpu_frac=coord.get("lighthouse_cpu_frac"),
        )

    obs: Dict[str, Any] = {}
    if not os.environ.get("TPUFT_BENCH_SKIP_OBS"):
        # observability-overhead gate (ISSUE 14): pure host-side micro
        # phase, seconds regardless of platform — runs even when the fleet
        # block was skipped
        try:
            obs = _run_obs_phase()
        except Exception as e:  # noqa: BLE001 — a failed phase is a
            # recorded fact, never a lost artifact
            obs = {"error": f"{type(e).__name__}: {e}"}
        print(f"bench: obs overhead {obs}", file=sys.stderr)
        # the headline key streams TOP-LEVEL the moment the phase lands
        _emit_partial(
            obs=obs, obs_overhead_frac=obs.get("obs_overhead_frac")
        )

    if ratio is None:
        # fleet phases unusable: fall back to the ws=1 protocol ratio so the
        # bench always reports something honest
        ratio = single["ws1_ratio"]
        faults.setdefault("note", "fleet phases incomplete; value is ws=1 ratio")
        metric = "ft_vs_faultfree_tokens_per_sec_ratio"
    else:
        # BASELINE's contract: sustained throughput under one replica kill
        # per 100 steps, measured from the survivor's steady step time and
        # the per-kill disruption overhead (see _fleet_metrics)
        metric = "ft_withfaults_vs_faultfree_tokens_per_sec_ratio_100step_kill"

    qdr_active, qdr_reason = _quant_device_reduce_active()
    out = {
        "metric": metric,
        "value": round(ratio, 4),
        "unit": "ratio",
        "vs_baseline": round(ratio / 0.95, 4),
        # which quantized-allreduce reduction path this env would run
        # (device Pallas dequant-sum-requant vs host)
        "quant_device_reduce": qdr_active,
        "quant_device_reduce_reason": qdr_reason,
        **single,
    }
    if faults:
        out["faults"] = faults
        if "mean_heal_in_s" in faults:
            out["mean_heal_in_s"] = faults["mean_heal_in_s"]
    if diloco:
        out["diloco"] = diloco
    if coord:
        out["coord"] = coord
    if obs:
        out["obs"] = obs
    # FULL detail goes to bench_out.json; stdout gets ONE compact headline
    # object (<= ~1 KB) as the LAST line, so a driver that captures only a
    # 2000-char output tail always holds one complete parseable JSON
    # (rounds 3 AND 4 lost the artifact head to exactly that truncation)
    _emit_partial(final=out)
    headline = {
        "metric": out["metric"],
        "value": out["value"],
        "unit": "ratio",
        "vs_baseline": out["vs_baseline"],
        "platform": single.get("platform"),
        "device_kind": single.get("device_kind"),
        "tier": single.get("tier"),
        "mfu": single.get("mfu"),
        "mfu_ft": single.get("mfu_ft"),
        "model_tflops_per_sec": single.get("model_tflops_per_sec"),
        "faultfree_tokens_per_sec": single.get("faultfree_tokens_per_sec"),
        "ws1_ratio": single.get("ws1_ratio"),
        "remat": single.get("remat"),
        "mean_heal_in_s": out.get("mean_heal_in_s"),
        "heal_in_s_by_path": (faults.get("faulted_fleet") or {}).get(
            "heal_in_s_by_path"
        ),
        # heal_breakdown phases as top-level keys (round-over-round
        # comparable without opening bench_out.json), and the hot-spare
        # promotion heal-in NEXT TO the cold fleet heal-in — the PR-6
        # payoff measured side by side
        **_headline_heal_keys(faults),
        "spare_mean_heal_in_s": (faults.get("spare_promotion") or {}).get(
            "mean_heal_in_s"
        ),
        "spare_warm_lag_steps": (faults.get("spare_promotion") or {}).get(
            "warm_lag_steps"
        ),
        "kills": faults.get("kills"),
        "diloco_ratio": diloco.get("ratio_per_100step_kill"),
        "diloco_kills": diloco.get("kills_in_sync_window"),
        # PR-5 trajectory: outer sync cost, sharded vs replicated
        "sync_overhead_s_sharded": diloco.get("sync_overhead_s_sharded"),
        "sync_overhead_s_replicated": diloco.get("sync_overhead_s_replicated"),
        # ISSUE-15 streamed outer sync: residual barrier cost, overlap
        # win, and the fraction-of-an-inner-step headline (§18 gate 0.05)
        "sync_overhead_s_streaming": diloco.get("sync_overhead_s_streaming"),
        "stream_overlap_ratio": diloco.get("stream_overlap_ratio"),
        "sync_overhead_frac": diloco.get("sync_overhead_frac"),
        # ISSUE-12 coordination plane: quorum latency through churn at
        # scale, lighthouse CPU, and the aggregation RPC win
        "coord_p99_quorum_latency_s": coord.get("p99_quorum_latency_s"),
        "lighthouse_cpu_frac": coord.get("lighthouse_cpu_frac"),
        "coord_rpc_reduction": coord.get("rpc_reduction_vs_direct"),
        # ISSUE-14 observability plane: recorder+spans fully enabled must
        # cost <= 1% step time (the obs phase's measured fraction)
        "obs_overhead_frac": obs.get("obs_overhead_frac"),
        "quant_device_reduce": qdr_active,
        "detail": "bench_out.json",
    }
    blob = json.dumps(headline)
    if len(blob) > 1900:  # belt-and-braces: never outgrow a tail capture
        for k in (
            "heal_in_s_by_path",
            "remat",
            "ws1_ratio",
            "tier",
            "heal_respawn_s",
            "heal_join_s",
            "heal_transfer_s",
            "heal_first_commit_s",
            "heal_promote_s",
            "spare_warm_lag_steps",
        ):
            headline.pop(k, None)
        blob = json.dumps(headline)
    print(blob)


def _quant_device_reduce_active() -> Tuple[bool, str]:
    """(active, reason) for the Pallas dequant-sum-requant path at a 1 MB
    shard."""
    import jax

    from torchft_tpu.collectives import DEVICE_REDUCE_ENV, _use_device_reduce

    active = bool(_use_device_reduce(1 << 20))
    mode = os.environ.get(DEVICE_REDUCE_ENV, "")
    if mode == "0":
        return active, "forced off via env"
    if mode == "1":
        return active, "forced on via env"
    if jax.default_backend() != "tpu":
        return active, "off: backend is not tpu"
    return active, "auto (tpu backend, >=256KiB shards)"


def _budget_left(
    deadline_ts: Optional[float], frac: float, floor: float
) -> Optional[float]:
    """A fleet's share of what's left of the phase budget (None = no
    bound) — one policy for the fault-free and churn fleets alike.

    The floor keeps a phase viable when an earlier phase ran long, but only
    spends budget that actually remains: once the deadline is near/past the
    phase is capped at what is left (a token 30 s minimum), so stacked
    floors can no longer push total wall clock minutes past
    TPUFT_BENCH_TOTAL_BUDGET_S — the r05 bench exited rc=124 to exactly
    that.  Worst-case overrun is now the one phase that straddles the
    deadline (<= its own floor) plus teardown; drivers should size kill
    timeouts to budget + 180 s + margin.
    """
    if deadline_ts is None:
        return None
    remaining = deadline_ts - time.time()
    if remaining <= 30.0:
        return 30.0
    return max(min(floor, remaining), remaining * frac)


def _run_diloco_phase(
    sizes: Dict[str, int],
    worker_platform: Optional[str],
    replicas: int,
    deadline_ts: Optional[float] = None,
) -> Dict[str, Any]:
    """Phase D: Streaming DiLoCo islands, fault-free vs churn with kills
    timed into the fragment-sync window (BASELINE config 4).

    The quantized pseudogradient wire is gated on MEASURED benefit: in the
    default "auto" mode the fault-free fleet runs once per wire (f32 and
    int8/fp8), both sync overheads are recorded, and the churn run uses the
    wire that costs less per sync on this link — quantization spends host
    cycles that a fat loopback never repays, while over a thin DCN the 4x
    payload cut wins (the reference keeps quantization opt-in for the same
    reason, ``torchft/manager.py:457-468``)."""
    mode = _diloco_quant_env()
    ff_target = max(12, sizes["diloco_steps"] // 2)

    def _faultfree(tag: str, quant: bool) -> Dict[str, Any]:
        r = run_fleet(
            f"diloco_faultfree_{tag}",
            target_steps=ff_target,
            sizes=sizes,
            worker_platform=worker_platform,
            replicas=replicas,
            mode="diloco",
            extra_env={"TPUFT_BENCH_DILOCO_QUANT_WIRE": "1" if quant else "0"},
            deadline_s=_budget_left(deadline_ts, 0.25, 90.0),
        )
        print(f"bench: diloco fault-free [{tag}] {r}", file=sys.stderr)
        # stream EVERY sub-leg into the artifact the moment it lands: the
        # round-5 loss was per-scenario numbers that existed only on
        # stderr when the run died between diloco legs
        _emit_partial(**{f"diloco_faultfree_{tag}": r})
        return r

    ff_by_wire: Dict[str, Dict[str, Any]] = {}
    if mode == "auto":
        ff_by_wire["f32"] = _faultfree("f32", quant=False)
        budget_left = (
            None if deadline_ts is None else deadline_ts - time.time()
        )
        if budget_left is not None and budget_left < 360.0:
            # starve the A/B before the churn run, never the reverse — the
            # churn ratio is the phase's headline number
            faultfree = ff_by_wire["f32"]
            use_quant = False
            gate = "auto"
            gate_reason = (
                f"quant A/B skipped: {budget_left:.0f}s of budget left is "
                "reserved for the churn run"
            )
            return _diloco_churn_and_summary(
                sizes, worker_platform, replicas, deadline_ts,
                ff_by_wire, faultfree, use_quant, gate, gate_reason,
            )
        ff_by_wire["quant"] = _faultfree("quant", quant=True)
        so_f = ff_by_wire["f32"].get("sync_overhead_s")
        so_q = ff_by_wire["quant"].get("sync_overhead_s")
        # use the quantized wire when it is at least as cheap per sync
        # (within 10% counts: the payload cut is worth noise-level host tax)
        if so_f is not None and so_q is not None:
            use_quant = so_q <= so_f * 1.1
            gate_reason = f"measured: quant {so_q}s vs f32 {so_f}s per sync"
        else:
            use_quant = False
            gate_reason = (
                "gate fell back to f32: sync_overhead_s missing "
                f"(quant={so_q}, f32={so_f}) — too few committed sync steps"
            )
        gate = "auto"
    else:
        use_quant = mode == "1"
        ff_by_wire["quant" if use_quant else "f32"] = _faultfree(
            "quant" if use_quant else "f32", quant=use_quant
        )
        gate = "forced"
        gate_reason = f"TPUFT_BENCH_DILOCO_QUANT={mode}"
    faultfree = ff_by_wire["quant" if use_quant else "f32"]
    # sharded-vs-replicated sync overhead (docs/operations.md §11): one
    # extra fault-free leg pins TORCHFT_OUTER_SHARD=0 (the legacy
    # replicated outer update) on the chosen wire, so the PR-5 perf
    # trajectory is machine-readable in the artifact round over round.
    # Budget-guarded like the quant A/B — the churn run is the phase's
    # headline and is never starved for this row.
    budget_left = None if deadline_ts is None else deadline_ts - time.time()
    if _outer_shard_mode_env() != "0" and (
        budget_left is None or budget_left >= 360.0
    ):
        # when the session itself pins the legacy path the main legs ARE
        # replicated — an extra pinned leg would be a meaningless A/A burn
        ff_by_wire["replicated"] = run_fleet(
            "diloco_faultfree_replicated",
            target_steps=ff_target,
            sizes=sizes,
            worker_platform=worker_platform,
            replicas=replicas,
            mode="diloco",
            extra_env={
                "TPUFT_BENCH_DILOCO_QUANT_WIRE": "1" if use_quant else "0",
                "TORCHFT_OUTER_SHARD": "0",
            },
            deadline_s=_budget_left(deadline_ts, 0.25, 90.0),
        )
        print(
            f"bench: diloco fault-free [replicated] "
            f"{ff_by_wire['replicated']}",
            file=sys.stderr,
        )
        _emit_partial(diloco_faultfree_replicated=ff_by_wire["replicated"])
    # ISSUE-15 streamed outer sync (docs/operations.md §18): one more leg
    # on the chosen wire with the fragment scheduler forced on, so the
    # artifact carries blocking-vs-streamed residual sync cost round over
    # round.  Budget-guarded like the other A/B rows — churn is never
    # starved for it — and TPUFT_BENCH_SKIP_STREAM=1 opts out.
    budget_left = None if deadline_ts is None else deadline_ts - time.time()
    per_frag = max(
        1, sizes["diloco_sync_every"] // max(1, sizes["diloco_fragments"])
    )
    stall_room = per_frag - sizes["diloco_sync_delay"] - 1
    if (
        not os.environ.get("TPUFT_BENCH_SKIP_STREAM")
        and stall_room >= 1
        and (budget_left is None or budget_left >= 360.0)
    ):
        ff_by_wire["streaming"] = run_fleet(
            "diloco_faultfree_streaming",
            target_steps=ff_target,
            sizes=sizes,
            worker_platform=worker_platform,
            replicas=replicas,
            mode="diloco",
            extra_env={
                "TPUFT_BENCH_DILOCO_QUANT_WIRE": "1" if use_quant else "0",
                "TORCHFT_STREAM_SYNC": "1",
                "TORCHFT_STREAM_MAX_STALENESS": str(stall_room),
            },
            deadline_s=_budget_left(deadline_ts, 0.25, 90.0),
        )
        print(
            f"bench: diloco fault-free [streaming] "
            f"{ff_by_wire['streaming']}",
            file=sys.stderr,
        )
        # the BENCH_r05 lesson: stream the leg into the partial artifact
        # the moment it lands, never only into the final assembly
        _emit_partial(diloco_faultfree_streaming=ff_by_wire["streaming"])
    elif not os.environ.get("TPUFT_BENCH_SKIP_STREAM") and stall_room < 1:
        print(
            "bench: diloco streaming leg skipped — cadence has no "
            f"staleness room (per_frag={per_frag}, "
            f"delay={sizes['diloco_sync_delay']})",
            file=sys.stderr,
        )
    return _diloco_churn_and_summary(
        sizes, worker_platform, replicas, deadline_ts,
        ff_by_wire, faultfree, use_quant, gate, gate_reason,
    )


def _diloco_churn_and_summary(
    sizes: Dict[str, int],
    worker_platform: Optional[str],
    replicas: int,
    deadline_ts: Optional[float],
    ff_by_wire: Dict[str, Dict[str, Any]],
    faultfree: Dict[str, Any],
    use_quant: bool,
    gate: str,
    gate_reason: str,
) -> Dict[str, Any]:
    """Churn run + phase-D artifact assembly, shared by the full A/B path
    and the budget-starved early path."""
    churn = run_fleet(
        "diloco_churn",
        target_steps=sizes["diloco_steps"],
        sizes=sizes,
        worker_platform=worker_platform,
        replicas=replicas,
        mode="diloco",
        kill_every=max(
            sizes["diloco_sync_every"],
            sizes["diloco_steps"] // (sizes["diloco_kills"] + 1),
        ),
        kill_in_sync_window=True,
        max_kills=sizes["diloco_kills"],
        extra_env={"TPUFT_BENCH_DILOCO_QUANT_WIRE": "1" if use_quant else "0"},
        deadline_s=_budget_left(deadline_ts, 0.9, 180.0),
    )
    print(f"bench: diloco churn {churn}", file=sys.stderr)
    _emit_partial(diloco_churn=churn)
    out: Dict[str, Any] = {
        "sync_every": sizes["diloco_sync_every"],
        "fragments": sizes["diloco_fragments"],
        "fragment_sync_delay": sizes["diloco_sync_delay"],
        "quantized_sync": use_quant,
        "quant_gate": gate,
        "quant_gate_reason": gate_reason,
        "quant_kind": _quant_kind_or_error(),
        "kills_in_sync_window": churn.get("kills", 0),
        "faultfree": faultfree,
        "churn": churn,
    }
    # the alternate wire's fleet run is never discarded: both runs (and
    # whatever overheads they produced) land in the artifact even when the
    # gate had to fall back
    alt_wire = "f32" if use_quant else "quant"
    if alt_wire in ff_by_wire:
        out["faultfree_alt"] = ff_by_wire[alt_wire]
    for wire, r in ff_by_wire.items():
        if r.get("sync_overhead_s") is not None:
            out[f"sync_overhead_s_{wire}"] = r["sync_overhead_s"]
    # the f32/quant legs run with the session's TORCHFT_OUTER_SHARD
    # (default auto = sharded); the "replicated" leg pinned =0.  Emit the
    # chosen wire's overhead under a stable "sharded" name next to the
    # replicated row so BENCH artifacts compare like for like.
    shard_mode = _outer_shard_mode_env()
    out["outer_shard_mode"] = shard_mode
    if faultfree.get("sync_overhead_s") is not None:
        if shard_mode != "0":
            out["sync_overhead_s_sharded"] = faultfree["sync_overhead_s"]
        else:
            # pinned-legacy session: the chosen wire's leg ran replicated
            out.setdefault(
                "sync_overhead_s_replicated", faultfree["sync_overhead_s"]
            )
    so_r = out.get("sync_overhead_s_replicated")
    so_s = out.get("sync_overhead_s_sharded")
    if so_r is not None and so_s is not None:
        out["sharded_vs_replicated_sync_overhead"] = round(
            so_r / max(so_s, 1e-4), 3
        )
    # ISSUE-15 streamed outer sync: the residual barrier cost, how much of
    # the blocking sync it hid, and the headline fraction of an inner step
    # the residual represents (the §18 gate is <= 0.05 under wan_1g)
    stream_leg = ff_by_wire.get("streaming")
    so_stream = out.get("sync_overhead_s_streaming")
    if so_stream is not None:
        blocking = so_s if so_s is not None else so_r
        if blocking is not None and blocking > 1e-4:
            out["stream_overlap_ratio"] = round(
                min(1.0, max(0.0, 1.0 - so_stream / blocking)), 3
            )
        inner_s = stream_leg.get("inner_step_s") or stream_leg.get(
            "t_step_s"
        )
        if inner_s:
            out["sync_overhead_frac"] = round(
                so_stream / max(float(inner_s), 1e-6), 4
            )
    if "sync_overhead_s_f32" in out and "sync_overhead_s_quant" in out:
        base = max(out["sync_overhead_s_f32"], 1e-4)
        out["quant_vs_f32_sync_overhead"] = round(
            out["sync_overhead_s_quant"] / base, 3
        )
    tf = faultfree.get("t_step_s")
    tc = churn.get("t_step_s")
    if tf and tc:
        out["inner_step_ratio"] = round(tf / tc, 4)
    if faultfree.get("sync_overhead_s") is not None:
        out["sync_overhead_s"] = faultfree["sync_overhead_s"]
    if churn.get("ratio_per_100step_kill") is not None:
        out["ratio_per_100step_kill"] = churn["ratio_per_100step_kill"]
    if churn.get("mean_heal_in_s") is not None:
        out["mean_heal_in_s"] = churn["mean_heal_in_s"]
    return out


if __name__ == "__main__":
    if "--worker" in sys.argv:
        worker_main()
    else:
        main()
