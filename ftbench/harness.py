"""The runner: replica groups as threads of this process, one cell a run.

The replica loop is the shape of ``chip_smoke.py`` leg C's (a Manager and
an ``HSDPTrainer`` per life, a kill by exception, a restart with other
weights and a live heal), without printing or digests between the steps.
Everything a step leaves behind is a tuple on a list; files, digests, the
reference check and ``memory_stats`` come after the window has closed.

Step numbers are the fleet's (``manager.current_step()`` after a commit).
Replica 0 leads: it decides when the window opens and closes, always one
step ahead, so that every replica reads the decision after a commit that
replica 0 took part in and all stop at the same step.
"""

from __future__ import annotations

import gc
import hashlib
import json
import os
import statistics
import sys
import threading
import time
import traceback
from typing import Any, Dict, List, Optional, Tuple

from ftbench import accounting, trace_reduce
from ftbench.spec import Cell, load_metric

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

# the program's forward pass against the float32 reference, after the window
# (``forward_passes``, ``reference_verdict``; README.md, "How `correct` is
# decided").  Compared: the cross-entropy of every position of one seeded
# batch at weights made anew from the seed, by the root mean square of the
# differences.  The yardstick is made in the same call: the same program on
# the same batch with its matrices through float8_e4m3fn and back
# (``coarse_copy``), and the program's differences have to stay K times
# under that copy's.  K is the architecture's (``COARSE_RATIO_K`` of the
# cell's file under ``architectures/``, with the two readings it lies
# between): how far a sound bfloat16 pass stands from an 8-bit one depends
# on the layers.  The two limits below are float32's and so the harness's.
# ``model.loss`` is what a training step differentiates, ``model.apply`` what
# the positions are read from: the verdict needs the mean over the positions
# to BE the program's loss, to float32's summation order (1.7e-6 at most in
# 53 chip runs), so that a change to a model's ``loss`` is never judged by a
# stale copy of it
LOSS_TIE_ABS = 2e-5
# float32 (the CPU rehearsal) has no 8-bit neighbour to be told from: the
# two mean losses differ only by summation order
REFERENCE_TOLERANCE_ABS_FLOAT32 = 2e-4


class _Killed(Exception):
    pass


class Control:
    """What the replica threads share.  Plain attributes, written by
    replica 0 only (``kills`` by the victim) and read by all."""

    def __init__(self) -> None:
        self.open_step: Optional[int] = None
        self.close_step: Optional[int] = None
        self.final_step: Optional[int] = None
        self.trace_stop_step: Optional[int] = None
        # one dict a kill: t_kill, then (from the new life) first_commit,
        # back_step, timings, heal, and that life's flight events
        self.kills: List[Dict[str, Any]] = []
        self.errors: List[BaseException] = []
        self.compiles = 0
        self.cache_hits = 0
        self.marks: Dict[str, Any] = {}


class Probe:
    """Host-clock stamps around the three Manager calls ``train_step``
    makes, taken by shadowing them on the instance: the program is not
    edited and runs its own ``train_step``."""

    def __init__(self, manager: Any) -> None:
        self.quorum_exit = 0.0
        self.commit = (0.0, 0.0)
        self.ring: List[List[float]] = []
        start_quorum, allreduce, should_commit = (
            manager.start_quorum,
            manager.allreduce,
            manager.should_commit,
        )

        def timed_start_quorum(*args: Any, **kwargs: Any) -> Any:
            out = start_quorum(*args, **kwargs)
            self.quorum_exit = time.monotonic()
            return out

        def timed_allreduce(*args: Any, **kwargs: Any) -> Any:
            slot = [time.monotonic(), 0.0]
            self.ring.append(slot)
            work = allreduce(*args, **kwargs)
            work.future().add_done_callback(
                lambda _f: slot.__setitem__(1, time.monotonic())
            )
            return work

        def timed_should_commit(*args: Any, **kwargs: Any) -> Any:
            t0 = time.monotonic()
            out = should_commit(*args, **kwargs)
            self.commit = (t0, time.monotonic())
            return out

        manager.start_quorum = timed_start_quorum
        manager.allreduce = timed_allreduce
        manager.should_commit = timed_should_commit

    def take(self) -> Tuple[float, Tuple[float, float], List[List[float]]]:
        out = (self.quorum_exit, self.commit, self.ring)
        self.ring = []
        return out


def key_int(seed: int, *more: int) -> int:
    """A 31-bit PRNG seed from ``--seed`` (which may pass 2**31) and a
    replica or life number."""
    import numpy as np

    return int(np.random.SeedSequence([seed, *more]).generate_state(1)[0] & 0x7FFFFFFF)


def seeded_batch(rng_seed: int, vocab: int, rows: int, seq: int, batch_sh: Any) -> Tuple[Any, Any, Tuple]:
    """(tokens, targets, the two on the device) of uniform token ids."""
    import jax
    import numpy as np

    tokens = np.random.default_rng(rng_seed).integers(0, vocab, size=(rows, seq)).astype(np.int32)
    targets = np.roll(tokens, -1, axis=1)
    return tokens, targets, tuple(jax.device_put(b, sh) for b, sh in zip((tokens, targets), batch_sh))


def digest(host_leaves: List[Any]) -> str:
    import numpy as np

    sha = hashlib.sha256()
    for leaf in host_leaves:
        sha.update(np.ascontiguousarray(leaf).reshape(-1).view(np.uint8))
    return sha.hexdigest()[:16]


E4M3_MAX = 448.0  # float8_e4m3fn: 4 bits of exponent, 3 of mantissa, no infinity


def _is_matrix(x: Any) -> bool:
    import jax.numpy as jnp

    return x.dtype == jnp.bfloat16 and x.ndim >= 2


def _e4m3_scale(x: Any) -> Any:
    """What brings each matrix of ``x`` (a leaf's last two axes: a stacked
    leaf is one matrix a layer) to float8_e4m3fn's range."""
    import jax.numpy as jnp

    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=(-2, -1), keepdims=True)
    return jnp.where(amax > 0, E4M3_MAX / amax, 1.0)


def to_e4m3(params: Any) -> Any:
    import jax
    import jax.numpy as jnp

    return jax.tree_util.tree_map(
        lambda x: (x.astype(jnp.float32) * _e4m3_scale(x)).astype(jnp.float8_e4m3fn)
        if _is_matrix(x) else x,
        params,
    )


def from_e4m3(params8: Any, params: Any) -> Any:
    import jax
    import jax.numpy as jnp

    return jax.tree_util.tree_map(
        lambda q, x: (q.astype(jnp.float32) / _e4m3_scale(x)).astype(x.dtype)
        if _is_matrix(x) else x,
        params8, params,
    )


def coarse_copy(params: Any, shardings: Any = None) -> Any:
    """``params`` as an 8-bit float path would hold them: every bfloat16
    leaf of two or more dimensions through ``float8_e4m3fn`` and back, each
    matrix scaled so that its largest magnitude is the format's largest.
    Norms (float32) and vectors stay as they are.  Two programs with the
    float8 leaves in memory between them: inside ONE fusion the v5e's
    compiler keeps a float8 intermediate at full width, and a cast there and
    back rounds nothing (PERF.md section 6, PR 25)."""
    import jax

    return jax.jit(from_e4m3, out_shardings=shardings)(jax.jit(to_e4m3)(params), params)


def system_token_nll(model: Any, params: Any, batch: Tuple[Any, Any]) -> Any:
    """The cross-entropy of every position, [B, S], by the program's own
    forward pass (``model.apply``)."""
    import jax
    import jax.numpy as jnp

    tokens, targets = batch
    logp = jax.nn.log_softmax(model.apply(params, tokens), axis=-1)
    return -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]


def forward_passes(
    arch: Any, model: Any, mesh: Any, config: Dict[str, Any], seed: int, rows: int,
    seq: int, copies: Dict[str, Any],
) -> Tuple[float, Dict[str, Any]]:
    """``model.loss`` and the cross-entropy of every position, on one batch
    and at weights that both come from ``seed`` alone: by the program
    (``system``), by the program on each copy of the weights that ``copies``
    names (name to ``f(params, shardings)``), and by the plain reference in
    float32 (``reference``: ``arch.token_nll``, the architecture's own).
    Nothing a window has trained is read."""
    import jax
    import numpy as np

    from torchft_tpu.parallel.hsdp import fsdp_shardings

    params_sh, batch_sh = fsdp_shardings(model, mesh)
    tokens, targets, batch = seeded_batch(
        key_int(seed, 7777), arch.vocab(config), rows, seq, batch_sh
    )
    nll_fn = jax.jit(lambda p, b: system_token_nll(model, p, b))
    nll: Dict[str, Any] = {}
    with mesh:
        params = jax.jit(model.init, out_shardings=params_sh)(
            jax.random.PRNGKey(key_int(seed, 8888))
        )
        system_loss = float(jax.jit(model.loss)(params, batch))
        nll["system"] = np.asarray(nll_fn(params, batch))
        for name, copy in copies.items():
            nll[name] = np.asarray(nll_fn(copy(params, params_sh), batch))
    host = jax.tree_util.tree_map(np.asarray, params)
    del params
    with jax.default_device(mesh.devices.flat[0]):
        nll["reference"] = np.asarray(arch.token_nll(host, tokens, targets, config))
    return system_loss, nll


def reference_verdict(
    system: Any, reference: Any, coarse: Optional[Any], system_loss: float, coarse_ratio_k: float
) -> Dict[str, Any]:
    """The arm by which ``reference_agrees`` holds (None: it does not), and
    every number behind it: from the cross-entropy of every position by the
    program, by the reference and by the program on the coarse copy, and from
    the program's own mean loss.  ``coarse`` is None where no copy was made
    (float32): the absolute arm on the means.  ``coarse_ratio_k`` is the
    architecture's ``COARSE_RATIO_K``."""
    import numpy as np

    s, r = (np.asarray(a, np.float64).ravel() for a in (system, reference))
    out: Dict[str, Any] = dict(
        reference_arm=None,
        system_loss=float(system_loss),
        reference_loss=float(r.mean()),
        loss_tie=float(abs(system_loss - s.mean())),
        loss_tie_abs=LOSS_TIE_ABS,
        token_rms=float(np.sqrt(np.mean((s - r) ** 2))),
    )
    # a NaN on any side compares false
    tied = out["loss_tie"] <= LOSS_TIE_ABS
    if coarse is None:
        out["reference_diff"] = float(abs(system_loss - r.mean()))
        out["reference_tolerance_abs"] = REFERENCE_TOLERANCE_ABS_FLOAT32
        if tied and out["reference_diff"] <= REFERENCE_TOLERANCE_ABS_FLOAT32:
            out["reference_arm"] = "absolute"
        return out
    c = np.asarray(coarse, np.float64).ravel()
    out.update(
        coarse_token_rms=float(np.sqrt(np.mean((c - r) ** 2))),
        coarse_ratio_k=coarse_ratio_k,
    )
    if out["token_rms"] > 0:
        out["coarse_ratio"] = out["coarse_token_rms"] / out["token_rms"]
    # a yardstick that equals the reference measures nothing (weights of zero)
    if tied and out["coarse_token_rms"] > 0 and out["token_rms"] <= out["coarse_token_rms"] / coarse_ratio_k:
        out["reference_arm"] = "coarse"
    return out


def compared_numbers(verdict: Dict[str, Any], compiles_in_window: int, digests: List[str]) -> Dict[str, Any]:
    """``name: [number, limit]`` of what ``correct`` compares as numbers; the
    rule itself is ``reference_verdict``'s and the checks'."""
    out: Dict[str, Any] = {"loss_tie_max": [verdict["loss_tie"], verdict["loss_tie_abs"]]}
    if "coarse_ratio_k" in verdict:
        # the program's differences, and the coarse copy's over K above them
        out["token_rms_max"] = [verdict["token_rms"], verdict["coarse_token_rms"] / verdict["coarse_ratio_k"]]
        out["coarse_ratio_min"] = [verdict.get("coarse_ratio"), verdict["coarse_ratio_k"]]
    else:
        out["reference_diff_max"] = [verdict["reference_diff"], verdict["reference_tolerance_abs"]]
    out["compiles_in_window_max"] = [compiles_in_window, 0]
    out["distinct_digests_max"] = [len(set(digests)), 1]
    return out


def _tx_bytes(comm: Any) -> int:
    return sum(comm.lane_stats().get("lane_tx_bytes", []))


RECORD_KEYS = (
    "life", "step", "committed", "t_enter", "t_exit", "loss",
    "quorum_exit", "commit", "ring", "quorum_rpc_s",
)


def as_dicts(records: List[Tuple]) -> List[Dict[str, Any]]:
    return [dict(zip(RECORD_KEYS, r)) for r in records]


def say(**fields: Any) -> None:
    print("ftbench: " + json.dumps(fields), flush=True)


def run_cell(
    cell: Cell, seed: int, seconds: float, trace: bool, rehearse: bool, t_process: float
) -> int:
    """Runs one cell and prints the result line.  Returns the exit code."""
    from torchft_tpu.utils.compile_cache import configure_compile_cache

    import jax

    cache_dir = configure_compile_cache()
    # every program, however quick to compile, is kept: a restarted life
    # inside the window then compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    devices = jax.devices()
    t_devices = time.monotonic()
    platform = devices[0].platform
    if platform != "tpu" and not rehearse:
        print(
            f"ftbench: JAX found no TPU (platform {platform!r}); nothing was "
            "run. --rehearse walks the cell on the CPU at toy size and "
            "prints no metric.",
            file=sys.stderr,
        )
        return 1
    if len(devices) < cell.chips:
        print(
            f"ftbench: cell {cell.name} needs {cell.chips} chips, JAX has "
            f"{len(devices)}; nothing was run.",
            file=sys.stderr,
        )
        return 1
    devices = devices[: cell.chips]

    config = dict(cell.config)
    traffic = cell.traffic
    seq = traffic["seq_len"]
    if rehearse:
        config.update(cell.architecture.TOY["config"])
        seq = cell.architecture.TOY["seq_len"]
    layout = config["layout"]
    n_replicas = traffic["replicas"]
    per_group = layout["chips_per_group"]
    if layout["groups_share_chip"]:
        groups = [devices[:per_group]] * n_replicas
    else:
        groups = [devices[i * per_group : (i + 1) * per_group] for i in range(n_replicas)]
    if any(len(g) != per_group for g in groups) or (
        not layout["groups_share_chip"] and n_replicas * per_group != cell.chips
    ):
        raise ValueError(
            f"{n_replicas} replica groups of {per_group} chips do not lay out "
            f"on the cell's {cell.chips} chips"
        )
    kill = traffic.get("kill")
    if kill and kill["victim"] == 0:
        raise ValueError("replica 0 leads the run and cannot be the victim")
    if per_group > 1 and n_replicas > 1 and not layout["groups_share_chip"]:
        # a program with collectives that JAX's persistent cache hands back
        # HALTS a group whose chips are not the host's first ("Core halted
        # unexpectedly ... enhanced-barrier"; compiled in the process it runs
        # without fault: README.md, "On four chips"; PERF.md section 6, PR 43).
        # Such a cell compiles its programs in every run, as set-up
        jax.config.update("jax_enable_compilation_cache", False)
        cache_dir = "off: a cached program halts a later group's chips"

    ctl = Control()
    ctl.marks["setup"] = {"jax_devices_s": t_devices - t_process}

    def on_duration(event: str, _seconds: float, **_kw: Any) -> None:
        if event == BACKEND_COMPILE_EVENT:
            ctl.compiles += 1

    def on_event(event: str, **_kw: Any) -> None:
        if event == CACHE_HIT_EVENT:
            ctl.cache_hits += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)

    fleet = Fleet(cell, config, traffic, seq, groups, seed, seconds, trace, rehearse, ctl)
    try:
        fleet.run()
    except BaseException:  # noqa: BLE001 — a failed run prints no result
        traceback.print_exc()
        sys.stdout.flush()
        sys.stderr.flush()
        # a replica thread may still be parked inside a collective
        os._exit(1)
    return fleet.report(t_process, cache_dir)


class Fleet:
    def __init__(
        self,
        cell: Cell,
        config: Dict[str, Any],
        traffic: Dict[str, Any],
        seq: int,
        groups: List[List[Any]],
        seed: int,
        seconds: float,
        trace: bool,
        rehearse: bool,
        ctl: Control,
    ) -> None:
        self.cell, self.config, self.traffic = cell, config, traffic
        self.arch = cell.architecture
        self.seq, self.groups, self.seed = seq, groups, seed
        self.seconds, self.trace, self.rehearse, self.ctl = seconds, trace, rehearse, ctl
        self.kill = traffic.get("kill")
        n = len(groups)
        # per replica: (life, step, committed, t_enter, t_exit, loss,
        #               quorum_exit, (commit_enter, commit_exit), ring, quorum_rpc_s)
        self.records: List[List[Tuple]] = [[] for _ in range(n)]
        self.lane_tx: List[Dict[str, int]] = [{} for _ in range(n)]
        self.final: List[Dict[str, Any]] = [{} for _ in range(n)]
        self.managers: List[Any] = []
        self.trace_dir = os.path.join(cell.bench_dir, "out", "trace")
        self.after: Dict[str, Any] = {}

    # -- the run -----------------------------------------------------------

    def run(self) -> None:
        from torchft_tpu import native, tier as tier_mod

        self.tier = tier_mod.default_tier()
        if not self.rehearse and not (
            self.tier == "cpp" and tier_mod.data_plane_tier() == "cpp"
        ):
            raise RuntimeError(
                f"native tier unavailable ({native.load_error()}): the cells "
                "measure the native data plane, not the Python tier"
            )
        lh = self.traffic["lighthouse"]
        self.lighthouse = tier_mod.make_lighthouse(
            bind="127.0.0.1:0",
            min_replicas=lh["min_replicas"],
            join_timeout_ms=lh["join_timeout_ms"],
            quorum_tick_ms=lh["quorum_tick_ms"],
            heartbeat_timeout_ms=lh["heartbeat_timeout_ms"],
            tier=self.tier,
        )
        threads = [
            threading.Thread(target=self._guarded, args=(i,), name=f"ftbench_replica_{i}", daemon=True)
            for i in range(len(self.groups))
        ]
        deadline = time.monotonic() + 1100.0
        try:
            for t in threads:
                t.start()
            while any(t.is_alive() for t in threads) and not self.ctl.errors:
                if time.monotonic() > deadline:
                    raise TimeoutError("the run passed 1100 s")
                time.sleep(0.05)
            if self.ctl.errors:
                raise self.ctl.errors[0]
            self._after_window()
        finally:
            gc.enable()
            gc.unfreeze()
            for m in list(self.managers):
                try:
                    m.shutdown()
                except Exception:  # noqa: BLE001 — best-effort teardown
                    pass
            self.lighthouse.shutdown()

    def _guarded(self, idx: int) -> None:
        import jax

        try:
            with jax.default_device(self.groups[idx][0]):
                self._replica(idx)
        except BaseException as e:  # noqa: BLE001 — raised again by run()
            self.ctl.errors.append(e)

    def _replica(self, idx: int) -> None:
        import jax
        import numpy as np
        import optax

        from torchft_tpu import tier as tier_mod
        from torchft_tpu.checkpointing.http_transport import HTTPTransport
        from torchft_tpu.manager import Manager
        from torchft_tpu.parallel.hsdp import HSDPTrainer, fsdp_shardings
        from torchft_tpu.parallel.mesh import make_mesh

        ctl, traffic, kill = self.ctl, self.traffic, self.kill
        leader = idx == 0
        group = self.groups[idx]
        mesh = make_mesh(fsdp=len(group), devices=group)
        model = self.arch.model(self.config)
        batch_sh = fsdp_shardings(model, mesh)[1]
        rows = len(group) * traffic["sequences_per_chip"]
        batch = seeded_batch(
            key_int(self.seed, 1000 + idx), self.arch.vocab(self.config), rows, self.seq, batch_sh
        )[2]
        mgr = traffic["manager"]
        records = self.records[idx]
        warm_left = traffic["warmup_steps"]
        seen_compiles = -1
        life = 0
        while True:
            comm = tier_mod.make_communicator(timeout_s=mgr["timeout_s"], tier=self.tier)
            transport = HTTPTransport(timeout=mgr["timeout_s"])
            manager = Manager(
                comm=comm,
                checkpoint_transport=transport,
                min_replica_size=1,
                timeout=mgr["timeout_s"],
                quorum_timeout=mgr["quorum_timeout_s"],
                connect_timeout=mgr["connect_timeout_s"],
                heartbeat_interval=mgr["heartbeat_interval_s"],
                replica_id=f"ftbench_{idx}",
                lighthouse_addr=self.lighthouse.local_address(),
                server_cls=tier_mod.manager_server_cls(self.tier),
            )
            self.managers.append(manager)
            probe = Probe(manager)
            trainer = HSDPTrainer(
                model,
                optax.adamw(self.config["assumed"]["learning_rate"]),
                mesh,
                manager,
                # a restarted life comes up with OTHER weights: only the
                # heal can make it equal to the survivor again
                key=jax.random.PRNGKey(key_int(self.seed, life)),
                quantize_outer=traffic["quantize_outer"],
            )
            if leader and "trainer_ready_s" not in ctl.marks["setup"]:
                ctl.marks["setup"]["trainer_ready_s"] = time.monotonic()
            try:
                while True:
                    t_enter = time.monotonic()
                    loss, committed = trainer.train_step(batch)
                    t_exit = time.monotonic()
                    step = manager.current_step()
                    records.append(
                        (life, step, committed, t_enter, t_exit, loss, *probe.take(),
                         manager.last_quorum_timings.get("quorum_rpc_s", 0.0))
                    )
                    if life and committed and "first_commit" not in ctl.kills[life - 1]:
                        heal = transport.last_heal_metrics
                        ctl.kills[life - 1].update(
                            first_commit=t_exit,
                            timings=dict(manager.last_quorum_timings),
                            heal=None if heal is None else (heal.bytes_total, heal.duration_s),
                            back_step=step,
                        )
                    if ctl.open_step == step:
                        self.lane_tx[idx]["open"] = _tx_bytes(comm)
                    if leader:
                        self._lead(step, t_exit)
                        if ctl.open_step is None:
                            # steady state: warm-up steps counted from the
                            # last step in which anything compiled
                            if ctl.compiles != seen_compiles or not committed:
                                seen_compiles = ctl.compiles
                                warm_left = traffic["warmup_steps"]
                            else:
                                warm_left -= 1
                            if warm_left <= 0 and step + 1 >= traffic.get("open_not_before_step", 0):
                                gc.collect()
                                gc.freeze()
                                gc.disable()
                                ctl.open_step = step + 1
                    if ctl.final_step is not None and step >= ctl.final_step:
                        jax.block_until_ready(trainer.holder["params"])
                        if life:
                            ctl.kills[life - 1]["events"] = comm.flight.snapshot()
                        self.lane_tx[idx]["final"] = _tx_bytes(comm)
                        self._keep_final(idx, model, manager, comm, trainer)
                        return
                    if kill and idx == kill["victim"] and self._kill_due(step):
                        ctl.kills.append(dict(t_kill=time.monotonic()))
                        raise _Killed()
            except _Killed:
                # as a dead process leaves them: servers and sockets gone,
                # heartbeats stop, no word to the lighthouse (there is no
                # leave call to make).  The dead life's weights go with it:
                # two lives of them do not fit.
                if life:
                    ctl.kills[life - 1]["events"] = comm.flight.snapshot()
                life += 1
                manager.shutdown()
                self.managers.remove(manager)
                del manager, trainer, probe, comm, transport
                # the collector is off during the window, and a Manager and
                # its trainer hold each other: without this the dead life's
                # 2 GB stay on the chip (three kills peaked at 15.5 of 16 GB)
                gc.collect()

    def _kill_due(self, step: int) -> bool:
        """The victim's question after a commit: the first kill a fixed
        number of steps after the window's opening, each later one a fixed
        number of steps after the last life's first commit."""
        ctl, kill = self.ctl, self.kill
        if ctl.open_step is None or len(ctl.kills) >= kill["count"]:
            return False
        if not ctl.kills:
            return step >= ctl.open_step + kill["after_window_steps"]
        return self._settled(ctl.kills[-1], step)

    def _settled(self, last_kill: Dict[str, Any], step: int) -> bool:
        return (
            "back_step" in last_kill
            and step >= last_kill["back_step"] + self.kill["steps_after_heal"]
        )

    def _lead(self, step: int, now: float) -> None:
        """Replica 0's decisions, each for a step that has not begun."""
        import jax

        ctl = self.ctl
        if ctl.open_step is None or step < ctl.open_step or ctl.final_step is not None:
            return
        if step == ctl.open_step:
            ctl.marks["open"] = dict(
                t=now, compiles=ctl.compiles, cache_hits=ctl.cache_hits,
                load=os.getloadavg(), cores=os.cpu_count(),
            )
            if self.trace:
                import shutil

                shutil.rmtree(self.trace_dir, ignore_errors=True)
                jax.profiler.start_trace(self.trace_dir)
                with jax.profiler.TraceAnnotation(trace_reduce.CLOCK_MARK):
                    ctl.marks["clock_host"] = time.monotonic()
                steps = self.traffic.get("trace_steps")
                ctl.trace_stop_step = None if steps is None else step + steps
        kill_done = not self.kill or (
            len(ctl.kills) == self.kill["count"] and self._settled(ctl.kills[-1], step)
        )
        elapsed = now - ctl.marks["open"]["t"]
        if not self.trace:
            due = elapsed >= self.seconds
        else:
            # a traced run is for the per-layer numbers: it closes when the
            # traced steps are done (a kill cell's when the kill has played out)
            if ctl.trace_stop_step is not None and step >= ctl.trace_stop_step:
                self._stop_trace(now)
            due = ctl.trace_stop_step is None or "trace_end" in ctl.marks
        if due and kill_done and step > ctl.open_step:
            if self.trace:
                self._stop_trace(now)
            ctl.close_step = step
            ctl.final_step = step + 1
            ctl.marks["close"] = dict(
                t=now, compiles=ctl.compiles, cache_hits=ctl.cache_hits,
                load=os.getloadavg(),
            )

    def _stop_trace(self, now: float) -> None:
        import jax

        if "trace_end" in self.ctl.marks:
            return
        self.ctl.marks["trace_end"] = now
        jax.profiler.stop_trace()

    def _keep_final(self, idx: int, model: Any, manager: Any, comm: Any, trainer: Any) -> None:
        import jax

        self.final[idx] = dict(
            model=model,
            trainer=trainer,
            errored=manager.errored(),
            events=comm.flight.snapshot(),
            attention_path=model.attention_path,
            device_sets={d for leaf in jax.tree_util.tree_leaves(trainer.holder) for d in leaf.devices()},
            state_bytes=sum(int(x.nbytes) for x in jax.tree_util.tree_leaves(trainer.holder)),
        )

    # -- after the window --------------------------------------------------

    def _after_window(self) -> None:
        import jax
        import numpy as np

        gc.enable()
        gc.unfreeze()
        used = {d for g in self.groups for d in g}
        stats = [d.memory_stats() or {} for d in used]
        self.after["peak_bytes"] = max((s.get("peak_bytes_in_use") or 0) for s in stats)
        self.after["limit_bytes"] = min((s.get("bytes_limit") or 0) for s in stats)
        # parameters equal across replicas: the average ran, the heal healed
        host = [
            jax.tree_util.tree_map(np.asarray, fin["trainer"].holder["params"])
            for fin in self.final
        ]
        self.after["digests"] = [digest(jax.tree_util.tree_leaves(h)) for h in host]
        self.after["grad_bytes"] = sum(
            int(x.nbytes) for x in jax.tree_util.tree_leaves(host[0])
        )
        # the program's forward pass against the plain reference, with the
        # 8-bit yardstick: made after memory_stats was read, dropped inside
        fin = self.final[0]
        rows = len(self.groups[0]) * self.traffic["sequences_per_chip"]
        # float32 (the rehearsal) has no leaf that an 8-bit copy would round
        coarse = any(_is_matrix(x) for x in jax.tree_util.tree_leaves(fin["trainer"].holder["params"]))
        system_loss, nll = forward_passes(
            self.arch, fin["model"], fin["trainer"].mesh, self.config, self.seed, rows, self.seq,
            {"coarse": coarse_copy} if coarse else {},
        )
        self.after.update(
            reference_verdict=reference_verdict(
                nll["system"], nll["reference"], nll.get("coarse"), system_loss,
                self.arch.COARSE_RATIO_K,
            ),
            # every position's numbers, for the series file
            token_nll={k: v.ravel().tolist() for k, v in nll.items()},
        )

    # -- the result --------------------------------------------------------

    def report(self, t_process: float, cache_dir: str) -> int:
        import jax

        ctl, cell = self.ctl, self.cell
        open_step, close_step = ctl.open_step, ctl.close_step
        device = jax.devices()[0]
        shapes = self.arch.shapes(self.config)
        tokens_per_step = len(self.groups[0]) * self.traffic["sequences_per_chip"] * self.seq

        window = [[r for r in recs if open_step < r[1] <= close_step] for recs in self.records]
        commits = [
            {r[1]: r[4] for r in recs if r[2]} for recs in self.records
        ]
        attempted = sum(len(w) for w in window)
        failed = sum(1 for w in window for r in w if not r[2])
        # a backend-compile event is recorded for a cache hit too
        in_window_hits = ctl.marks["close"]["cache_hits"] - ctl.marks["open"]["cache_hits"]
        in_window_compiles = (
            ctl.marks["close"]["compiles"] - ctl.marks["open"]["compiles"] - in_window_hits
        )
        verdict = self.after["reference_verdict"]
        checks = {
            "losses_finite": all(
                r[5] == r[5] and abs(r[5]) != float("inf") for recs in self.records for r in recs
            ),
            "digests_equal": len(set(self.after["digests"])) == 1,
            "no_compile_in_window": in_window_compiles == 0,
            "reference_agrees": verdict["reference_arm"] is not None,
            "no_manager_error": all(f["errored"] is None for f in self.final),
            # a kernel path of the architecture's, never a naive one
            "attention_flash": self.rehearse
            or all(f["attention_path"] in self.arch.KERNEL_PATHS for f in self.final),
            "devices_as_laid_out": all(
                f["device_sets"] == set(g) for f, g in zip(self.final, self.groups)
            ),
        }
        if self.kill:
            victim = self.kill["victim"]
            kills = ctl.kills
            # a step or two around the kill may be voted down, never a run
            runs, longest = 0, 0
            for r in self.records[0]:
                runs = 0 if r[2] else runs + 1
                longest = max(longest, runs)
            checks["kills_injected"] = len(kills) == self.kill["count"] and all(
                "first_commit" in k for k in kills
            )
            checks["few_uncommitted_around_kill"] = longest < 3
            checks["committed_before_kill"] = all(
                r[2] for recs in self.records for r in recs
                if open_step < r[1] and r[4] < kills[0]["t_kill"]
            )
        else:
            checks["every_step_committed"] = failed == 0

        sources: Dict[str, Any] = dict(
            cell=cell.name,
            chips=cell.chips,
            replicas=len(self.groups),
            groups_share_chip=self.config["layout"]["groups_share_chip"],
            # the cell's architecture file: a folded reader counts with ITS ``flops``
            architecture=self.arch,
            shapes=shapes,
            seq=self.seq,
            rows_per_replica=tokens_per_step // self.seq,
            tokens_per_step_per_replica=tokens_per_step,
            device_kind=device.device_kind,
            window=[as_dicts(w) for w in window],
            open_step=open_step,
            final_step=ctl.final_step,
            close_step=close_step,
            peak_bytes=self.after["peak_bytes"],
            grad_bytes_per_replica=self.after["grad_bytes"],
            lane_tx=self.lane_tx,
            kill=None,
            trace=None,
            # replica by replica, the flight events of the life the run ended with
            flight=[f["events"] for f in self.final],
        )
        end_to_end: Dict[str, float] = {
            "setup_s": ctl.marks["open"]["t"] - t_process,
        }
        if self.kill:
            survivor_commits = [r[4] for r in self.records[0] if r[2]]
            victim_after = [r[4] for r in self.records[victim] if r[0] > 0 and r[2]]

            # each kill by itself, then the mean over the run's kills
            end_to_end["resume_s"] = statistics.fmean(
                accounting.resume_s(k["t_kill"], victim_after) for k in kills
            )
            sources["kill"] = dict(
                kills=kills,
                survivor_events=self.final[0]["events"],
                survivor_commits=survivor_commits,
                state_bytes=self.final[victim]["state_bytes"],
            )
        else:
            stamps = [[c[s] for s in range(open_step, close_step + 1)] for c in commits]
            if not self.trace:
                # the close replica 0 chose while running is the one the rule gives
                checks["window_is_whole_steps"] = accounting.window_indices(
                    stamps[0], 0, self.seconds
                ) == (0, close_step - open_step)
            rate = accounting.tokens_per_s_per_chip(
                stamps, 0, close_step - open_step, tokens_per_step, cell.chips
            )
            for m in cell.end_to_end:
                if m["unit"] == "tokens/s":
                    end_to_end[m["name"]] = rate

        breakdown = None
        device_out = dict(
            platform=device.platform,
            kind=device.device_kind,
            count=len(jax.devices()),
            memory_peak_bytes=self.after["peak_bytes"],
        )
        if self.trace:
            sources["trace"], breakdown = self._reduce_trace(device_out, window)

        say(
            cell=cell.name, seed=self.seed, steps_in_window=close_step - open_step,
            window_s=ctl.marks["close"]["t"] - ctl.marks["open"]["t"],
            open_step=open_step, close_step=close_step,
            compiles_in_window=in_window_compiles,
            cache_hits_in_window=in_window_hits,
            compiles_total=ctl.compiles - ctl.cache_hits, cache_hits_total=ctl.cache_hits,
            cache_dir=cache_dir,
        )
        setup = ctl.marks["setup"]
        first = self.records[0][0]
        say(
            # set-up by phase: to jax.devices(), then to replica 0's trainer
            # (Manager, weights, optimizer state), its first step, the rest
            setup_jax_devices_s=setup["jax_devices_s"],
            setup_trainer_s=setup["trainer_ready_s"] - t_process - setup["jax_devices_s"],
            setup_first_step_s=first[4] - first[3],
            setup_warm_steps_s=ctl.marks["open"]["t"] - first[4],
        )
        say(
            host_cores=ctl.marks["open"]["cores"], load_at_open=ctl.marks["open"]["load"],
            load_at_close=ctl.marks["close"]["load"],
            memory_peak_bytes=self.after["peak_bytes"], memory_limit_bytes=self.after["limit_bytes"],
        )
        say(
            checks=checks, digests=self.after["digests"],
            **verdict, tier=self.tier,
            params_M=self.arch.num_params(self.config) / 1e6,
            attention=[f["attention_path"] for f in self.final],
        )

        metrics: Dict[str, Dict[str, Any]] = {}
        if self.trace:
            for m in cell.per_layer:
                metric = load_metric(m["name"], cell.bench_dir)
                value = metric.read(sources) if metric else None
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        else:
            for m in cell.end_to_end:
                if m["name"] in end_to_end:
                    metrics[m["name"]] = {"value": end_to_end[m["name"]], "unit": m["unit"]}
        self._write_series(end_to_end, checks, sources)

        result: Dict[str, Any] = dict(
            correct=all(checks.values()),
            attempted=attempted,
            failed=failed,
            metrics=metrics,
            device=device_out,
        )
        if breakdown:
            result["breakdown"] = breakdown
        # every number compared beside its limit, last in the line and last
        # on standard error: what a record of a run that is not correct keeps
        not_held = [name for name, held in checks.items() if not held]
        compared = compared_numbers(verdict, in_window_compiles, self.after["digests"])
        compared["checks_not_held_max"] = [len(not_held), 0]
        result["compared"] = compared
        print(f"ftbench: checks not held {not_held}", file=sys.stderr)
        for name, pair in compared.items():
            print(f"ftbench: compared {name} {json.dumps(pair)}", file=sys.stderr)
        sys.stderr.flush()
        if self.rehearse:
            # a CPU walk-through: no number of it may be read as a device's
            say(rehearsal=True, platform=device.platform, correct=result["correct"],
                attempted=attempted, failed=failed, would_report=sorted(metrics))
            return 0 if result["correct"] else 1
        print(json.dumps(result), flush=True)
        return 0

    def _reduce_trace(
        self, device_out: Dict[str, Any], window: List[List[Tuple]]
    ) -> Tuple[Optional[Dict], Optional[Dict]]:
        path = trace_reduce.find_xplane(self.trace_dir)
        if path is None:
            return None, None
        space = trace_reduce.load(path)
        marks = trace_reduce.clock_marks(space)
        # host stamps (time.monotonic) onto the trace's clock
        offset = marks[0][1] - self.ctl.marks["clock_host"] if marks else None
        per_device = trace_reduce.summarize(space)
        if not per_device:
            say(no_device_plane_in_trace=sorted(space))
            return None, None
        t0 = max(d["t0"] for d in per_device.values())
        t1 = min(d["t1"] for d in per_device.values())
        per_device = trace_reduce.summarize(space, t0, t1)
        busy = sum(d["busy_s"] for d in per_device.values()) / len(per_device)
        device_out["busy_s"], device_out["window_s"] = busy, t1 - t0
        phases = self._host_phases(offset) if offset is not None else []
        first = per_device[min(per_device)]
        # a while's event covers the operations of its body, which are
        # listed themselves
        totals = sorted(
            (kv for kv in trace_reduce.op_totals(first["ops"]).items() if not kv[0].startswith("%while")),
            key=lambda x: -x[1],
        )
        breakdown = dict(
            device_ops=[[n[:64], s] for n, s in totals[:10]],
            # idle seconds of the traced stretch by what replica 0's thread was doing
            idle_gaps=[
                [n, s]
                for n, s in trace_reduce.gap_totals(trace_reduce.name_gaps(first["gaps"], phases))[:10]
            ],
        )
        # the steps that lie whole inside the traced stretch
        traced = [
            as_dicts([r for r in w if offset is not None and t0 <= r[3] + offset and r[4] + offset <= t1])
            for w in window
        ]
        self.after["trace_shape"] = {
            plane: {
                line: [len(ev), sorted({e[0][:80] for e in ev})[: 400 if line == trace_reduce.OPS_LINE else 8]]
                for line, ev in lines.items()
            }
            for plane, lines in space.items()
            if trace_reduce.DEVICE_PLANE.match(plane)
        }
        return dict(per_device=per_device, t0=t0, t1=t1, offset=offset, traced_steps=traced), breakdown

    def _host_phases(self, offset: float) -> List[Tuple[str, float, float]]:
        """What the host was doing, per step of every replica, on the
        trace's clock: the names the idle gaps are given."""
        phases = []
        for recs in self.records[:1]:  # replica 0's thread: phases that do not overlap
            for r in recs:
                _, _, _, t_enter, t_exit, _, q_exit, (c0, c1), ring, _ = r
                if ring:
                    first, last = ring[0][0], max(s[1] for s in ring)
                    phases += [
                        ("grad_then_d2h", q_exit, first),
                        ("ring", first, last),
                        ("h2d_restore", last, c0),
                    ]
                else:
                    phases.append(("wait_for_grad", q_exit, c0))
                phases += [
                    ("quorum_start", t_enter, q_exit),
                    ("commit_vote", c0, c1),
                    ("update_dispatch", c1, t_exit),
                ]
        return [(n, a + offset, b + offset) for n, a, b in phases if b > a]

    def _write_series(self, end_to_end: Dict[str, float], checks: Dict[str, bool], sources: Dict) -> None:
        out_dir = os.path.join(self.cell.bench_dir, "out")
        os.makedirs(out_dir, exist_ok=True)
        name = f"{self.cell.name}-s{self.seed}-t{int(self.trace)}-{int(time.time())}.json"
        series = []
        for idx, recs in enumerate(self.records):
            for life, step, committed, t_enter, t_exit, loss, q_exit, (c0, c1), ring, q_rpc in recs:
                series.append(
                    dict(
                        replica=idx, life=life, step=step, committed=committed,
                        t_enter=t_enter, wall_s=t_exit - t_enter, loss=loss,
                        quorum_rpc_s=q_rpc, commit_s=c1 - c0,
                        grad_and_sync_s=c0 - q_exit,
                        ring_s=accounting.union_seconds([(a, b) for a, b in ring if b]),
                        ring_calls=len(ring),
                        in_window=self.ctl.open_step < step <= self.ctl.close_step,
                    )
                )
        with open(os.path.join(out_dir, name), "w") as f:
            json.dump(
                dict(
                    cell=self.cell.name, seed=self.seed, trace=self.trace,
                    seconds=self.seconds, end_to_end=end_to_end, checks=checks,
                    marks={k: v for k, v in self.ctl.marks.items()},
                    t_kills=[k["t_kill"] for k in self.ctl.kills], trace_shape=self.after.get("trace_shape"),
                    lane_tx=self.lane_tx, after={k: v for k, v in self.after.items() if k != "trace_shape"},
                    series=series,
                ),
                f,
            )
