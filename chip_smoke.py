"""chip_smoke.py — does the fault-tolerant HSDP path still start on the chip?

Drives the system's main path once, through the entry points a user calls,
at the widths of Llama-3-8B (depth cut to fit, the cut printed), and checks
what comes out by the repo's own means.  Three legs, in this order; the
first that does not hold ends the run with a non-zero exit code:

- **A** the CLI entry point in a child process, twice, while this process
  is still off JAX (a chip belongs to one process): ``python -m
  torchft_tpu.launcher --replicas 1 -- python examples/train_hsdp.py`` on
  every chip of the host.  Every step commits, the loss falls on a fixed
  batch, attention took the flash path, the data plane is the native tier,
  and the second run hits the compile cache the first one filled.
- **B** the Pallas kernels, compiled, against their references: flash
  forward, backward and the ``(o, lse)`` variant; int8 quantize, reduce and
  dequantize.  Each within its test tolerance AND with the Mosaic custom
  call in its compiled HLO.  fp8's verdict on this chip is printed.
- **C** two replica groups in this process, threads as replicas, each on its
  own devices (one chip: both share it, at a stated vocabulary cut; four:
  2 groups x 2 chips): real lighthouse, Managers and communicators over
  loopback TCP; steps on the plain ring, steps on the int8 wire, a kill, a
  restart, a live heal from the survivor.  Parameters equal across
  replicas before the kill and after the heal; every array of replica i on
  replica i's devices only.

No chip, no pass: without a TPU backend the script exits non-zero and
prints no result.  ``--dry-run`` is for developing the script and for the
CPU test suite: tiny sizes, kernels in interpret mode, ``dry_run=true
platform=cpu`` and never the pass line.  Seconds per step and wall times
are printed as information; this script defines no metric.

Last line of stdout on success:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import hashlib
import json
import math
import os
import signal
import subprocess
import sys
import threading
import time
import traceback
from typing import Any, Dict, Iterator, List, Optional

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

# wall-time bounds, seconds: each leg's own, and the whole run's (under the
# 1200 s a checker allows it, compilation included)
TOTAL_BOUND_S = 1150
PROBE_BOUND_S = 60
LEG_A_RUN_BOUND_S = 300
LEG_B_BOUND_S = 150
LEG_C_BOUND_S = 600

# leg C's schedule, in committed steps
PLAIN_STEPS = 2  # steps [0, 2) ride the plain ring
KILL_AT = 4  # steps [2, 4) ride the int8 wire, then replica 1 dies
TOTAL_STEPS = 8  # the survivor runs [4, 6) alone, both finish [6, 8)
VICTIM = 1


class LegFailed(Exception):
    pass


def _say(leg: str, **facts: Any) -> None:
    print(
        f"leg {leg}: " + " ".join(f"{k}={v}" for k, v in facts.items()),
        flush=True,
    )


def _require(cond: bool, what: str) -> None:
    if not cond:
        raise LegFailed(what)


# process groups this script started and has not seen end
_live_children: List[subprocess.Popen] = []


def _kill_children() -> None:
    for proc in _live_children:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass


@contextlib.contextmanager
def _deadline(what: str, seconds: float) -> Iterator[None]:
    """Bound work that runs in this process: compiles and collectives
    cannot be interrupted from Python, so past the bound the process says
    what hung, stops what it started and leaves."""

    def fire() -> None:
        print(
            f"FAIL {what}: exceeded its {seconds:.0f} s bound",
            file=sys.stderr,
            flush=True,
        )
        _kill_children()
        os._exit(1)

    timer = threading.Timer(seconds, fire)
    timer.daemon = True
    timer.start()
    try:
        yield
    finally:
        timer.cancel()


def _enter_dry_run() -> None:
    """The dry run's environment, set before this process or any child
    imports JAX."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=4"
    )
    # interpret-mode flash off TPU, so the dry run walks the same path
    os.environ["TORCHFT_FLASH"] = "1"
    # the toy compiles in under JAX's one-second caching threshold
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"


def _run_bounded(cmd: List[str], bound_s: float) -> subprocess.CompletedProcess:
    """Run ``cmd`` in its own process group and, at the bound, kill the
    whole group: the launcher's child must not outlive this script."""
    proc = subprocess.Popen(
        cmd,
        cwd=REPO,
        stdout=subprocess.PIPE,
        stderr=subprocess.PIPE,
        text=True,
        start_new_session=True,
    )
    _live_children.append(proc)
    try:
        out, err = proc.communicate(timeout=bound_s)
    except subprocess.TimeoutExpired:
        _kill_children()
        out, err = proc.communicate()
        raise LegFailed(
            f"{' '.join(cmd[:4])} ... exceeded its {bound_s:.0f} s bound; "
            f"stderr tail:\n{err[-3000:]}"
        ) from None
    finally:
        # the launcher ended: whatever it left behind goes with its group
        _kill_children()
        _live_children.remove(proc)
    return subprocess.CompletedProcess(cmd, proc.returncode, out, err)


def probe_device() -> Dict[str, Any]:
    """What JAX sees, asked of a child so this process stays off the chip."""
    src = (
        "import json, jax; d = jax.devices(); print(json.dumps({'platform': "
        "d[0].platform, 'kind': d[0].device_kind, 'count': len(d)}))"
    )
    done = _run_bounded([sys.executable, "-c", src], PROBE_BOUND_S)
    if done.returncode != 0:
        raise LegFailed(f"JAX did not start:\n{done.stderr[-3000:]}")
    return json.loads(done.stdout.strip().splitlines()[-1])


# --------------------------------------------------------------------------
# leg A: the CLI entry point, in a child
# --------------------------------------------------------------------------


def leg_a(device: Dict[str, Any], dry_run: bool) -> None:
    from torchft_tpu.utils.compile_cache import compile_cache_dir

    n = device["count"]
    size = (
        ["--model", "llama_debug", "--seq", "128"]
        if dry_run
        # llama3_8b widths; one of 32 layers is what 16 GB holds with AdamW
        else ["--model", "llama3_8b", "--n-layers", "1", "--seq", "2048"]
    )
    cmd = [
        sys.executable, "-m", "torchft_tpu.launcher",
        "--replicas", "1", "--max-restarts", "0", "--",
        sys.executable, os.path.join("examples", "train_hsdp.py"),
        *size,
        "--batch-size", str(n),  # one sequence per chip; mesh defaults to fsdp=n
        "--steps", "4",
        "--comm-timeout", "300",
    ]  # fmt: skip
    reports = []
    for run in ("cold", "warm"):
        t0 = time.perf_counter()
        done = _run_bounded(cmd, LEG_A_RUN_BOUND_S)
        wall = time.perf_counter() - t0
        _require(
            done.returncode == 0,
            f"{run} run exited {done.returncode}; stderr tail:\n"
            f"{done.stderr[-4000:]}",
        )
        line = next(
            (l for l in done.stdout.splitlines() if l.startswith("REPORT ")), None
        )
        _require(line is not None, f"{run} run printed no REPORT line")
        r = json.loads(line[len("REPORT "):])
        reports.append(r)
        _say(
            "A",
            run=run,
            platform=r["platform"],
            device_kind=repr(r["device_kind"]),
            devices=r["devices"],
            mesh=f"fsdp={r['mesh']['fsdp']}",
            n_layers=f"{r['n_layers']}/{r['published_n_layers']}",
            vocab=r["vocab_size"],
            tier=r["data_plane_tier"],
            attention=repr(r["attention"]),
            committed=f"{r['committed']}/{r['attempted']}",
            losses=r["losses"],
            peak_bytes=r["peak_bytes_in_use"],
            first_step_s=r["first_step_s"],
            step_s=r["step_s"],
            cache_hits=r["compile_cache_hits"],
            wall_s=round(wall, 1),
        )
        _require(
            r["platform"] == device["platform"] and r["devices"] == n,
            f"child ran on {r['platform']} x{r['devices']}, probe saw {device}",
        )
        _require(
            r["committed"] == r["attempted"] == 4,
            f"{r['committed']} of {r['attempted']} steps committed",
        )
        losses = r["losses"]
        _require(
            all(math.isfinite(l) for l in losses) and losses[-1] < losses[0],
            f"loss is not finite and falling on the fixed batch: {losses}",
        )
        _require(
            r["data_plane_tier"] == "cpp" and r["tier"] == "cpp",
            f"tier is {r['tier']}/{r['data_plane_tier']}, not the native one",
        )
        _require(
            r["attention"] == "flash", f"attention path: {r['attention']}"
        )
    entries = len(os.listdir(compile_cache_dir()))
    _say(
        "A",
        compile_cache=compile_cache_dir(),
        entries=entries,
        warm_run_hits=reports[1]["compile_cache_hits"],
    )
    _require(
        reports[1]["compile_cache_hits"] > 0,
        "the second run found nothing in the compile cache the first filled",
    )


# --------------------------------------------------------------------------
# leg B: kernels, compiled, against references
# --------------------------------------------------------------------------

_MOSAIC = "tpu_custom_call"


def _compiled(fn: Any, *args: Any, interpret: bool) -> Any:
    """Compile ``fn`` and prove the Mosaic kernel is in the executable (so
    neither interpret mode nor a jnp branch ran)."""
    import jax

    exe = jax.jit(fn).lower(*args).compile()
    if not interpret:
        _require(
            _MOSAIC in exe.as_text(),
            f"no {_MOSAIC} in the compiled HLO of {getattr(fn, '__name__', fn)}",
        )
    return exe


def _max_diff(a: Any, b: Any) -> float:
    import numpy as np

    return float(
        np.max(np.abs(np.asarray(a, np.float32) - np.asarray(b, np.float32)))
    )


def leg_b(dry_run: bool) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from torchft_tpu.models.llama import Llama, llama3_8b
    from torchft_tpu.ops import pallas_quant as pq
    from torchft_tpu.ops.flash_attention import flash_attention, flash_attention_lse

    interpret = dry_run
    dev = jax.devices()[0]
    # the model's attention shape: 32 query heads over 8 KV heads of 128
    cfg = llama3_8b()
    H, KV, D = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    S, block = (256, 128) if dry_run else (2048, 512)
    if dry_run:
        H, KV = 4, 2
    naive = Llama(dataclasses.replace(cfg, n_heads=H, n_kv_heads=KV, dim=H * D))
    naive._disable_flash = True  # Llama._attention's plain einsum-softmax path

    def ref_attention(q, k, v):
        return naive._attention(q, k, v, None)

    def flash(q, k, v):
        return flash_attention(
            q, k, v, block_q=block, block_k=block, interpret=interpret
        )

    def flash_lse(q, k, v):
        return flash_attention_lse(
            q, k, v, block_q=block, block_k=block, interpret=interpret
        )

    def grads_of(attn):
        # the sin keeps the cotangent from being a constant
        return jax.grad(
            lambda q, k, v: jnp.sum(jnp.sin(attn(q, k, v).astype(jnp.float32))),
            argnums=(0, 1, 2),
        )

    # (dtype, matmul precision, forward tolerance, backward tolerance).
    # float32 at the unit tests' tolerances catches wrong mathematics; the
    # MXU multiplies float32 in bfloat16 passes unless full precision is
    # asked for, and the kernel honours that request as XLA's own dots do,
    # so both sides are traced under it.  bfloat16 is what the model runs.
    for dtype, precision, tol_f, tol_b in (
        (jnp.float32, "highest", 2e-5, 1e-4),
        (jnp.bfloat16, None, 2e-2, 6e-2),
    ):
        keys = jax.random.split(jax.random.PRNGKey(0), 3)
        q, k, v = (
            jax.random.normal(kk, (1, S, h, D), dtype)
            for kk, h in zip(keys, (H, KV, KV))
        )
        with jax.default_matmul_precision("highest"):
            ref = jax.jit(ref_attention)(q, k, v)
            ref_g = jax.jit(grads_of(ref_attention))(q, k, v)
        with jax.default_matmul_precision(precision):
            out = _compiled(flash, q, k, v, interpret=interpret)(q, k, v)
            o2, lse = _compiled(flash_lse, q, k, v, interpret=interpret)(q, k, v)
            g = _compiled(grads_of(flash), q, k, v, interpret=interpret)(q, k, v)
        jax.block_until_ready((out, o2, lse, g))
        scale = float(jnp.max(jnp.abs(ref.astype(jnp.float32))))
        d_fwd, d_lse = _max_diff(out, ref), _max_diff(o2, ref)
        d_bwd = [_max_diff(a, b) for a, b in zip(g, ref_g)]
        g_scale = [float(jnp.max(jnp.abs(b.astype(jnp.float32)))) for b in ref_g]
        _say(
            "B",
            kernel="flash",
            dtype=jnp.dtype(dtype).name,
            precision=precision or "default",
            shape=f"S={S},H={H}/{KV},D={D},block={block}",
            fwd_diff=f"{d_fwd:.2e}",
            lse_variant_diff=f"{d_lse:.2e}",
            dq_dk_dv_diff=[f"{d:.2e}" for d in d_bwd],
            mosaic=not interpret,
        )
        _require(
            d_fwd <= tol_f * (1 + scale) and d_lse <= tol_f * (1 + scale),
            f"flash forward off by {d_fwd:.3e}/{d_lse:.3e} ({dtype.__name__})",
        )
        _require(
            bool(np.all(np.isfinite(np.asarray(lse)))), "flash lse not finite"
        )
        for name, d, gs in zip(("dq", "dk", "dv"), d_bwd, g_scale):
            _require(
                d <= tol_b * (1 + gs),
                f"flash {name} off by {d:.3e} ({dtype.__name__})",
            )

    # int8 rowwise quantization against the jnp mathematics the CPU tests
    # pin; an uneven tail exercises the row padding
    n = (64 if dry_run else 4096) * pq.ROW_SIZE + 77
    x = jax.random.normal(jax.random.PRNGKey(1), (n,), jnp.float32) * 3.0

    def quantize(x):
        return pq.quantize_rowwise_device(x, kind=pq.INT8, interpret=interpret)

    qv, sc = _compiled(quantize, x, interpret=interpret)(x)
    ref_q, ref_s = jax.jit(
        lambda x: pq._quant_math(pq._pad_to_rows(x, pq.ROW_SIZE)[0], pq.INT8)
    )(x)
    q_off = np.abs(np.asarray(qv, np.int32) - np.asarray(ref_q, np.int32))

    def dequantize(qv, sc):
        return pq.dequantize_rowwise_device(qv, sc, n=n, interpret=interpret)

    back = _compiled(dequantize, qv, sc, interpret=interpret)(qv, sc)
    ref_back = (ref_q.astype(jnp.float32) * ref_s).reshape(-1)[:n]

    def reduce(qs, ss):
        return pq.reduce_quantized_device(qs, ss, kind=pq.INT8, interpret=interpret)

    qs = jnp.stack([qv, jnp.flip(qv, axis=0)])
    ss = jnp.stack([sc, jnp.flip(sc, axis=0)])
    rq, rs = _compiled(reduce, qs, ss, interpret=interpret)(qs, ss)
    ref_rq, ref_rs = jax.jit(
        lambda qs, ss: pq._quant_math(
            jnp.sum(qs.astype(jnp.float32) * ss, axis=0), pq.INT8
        )
    )(qs, ss)
    r_off = np.abs(np.asarray(rq, np.int32) - np.asarray(ref_rq, np.int32))
    jax.block_until_ready((back, rq, rs))
    _say(
        "B",
        kernel="int8 quantize/dequantize/reduce",
        elements=n,
        quantize_max_off=int(q_off.max()),
        quantize_frac_off=f"{float((q_off > 0).mean()):.1e}",
        dequantize_diff=f"{_max_diff(back, ref_back):.2e}",
        reduce_max_off=int(r_off.max()),
        mosaic=not interpret,
    )
    # a division that rounds the other way on a tie may move a value by one
    # step on a different compiler; more than that is a different formula
    _require(
        int(q_off.max()) <= 1 and float((q_off > 0).mean()) < 1e-3,
        "int8 quantize disagrees with _quant_math",
    )
    np.testing.assert_allclose(np.asarray(sc), np.asarray(ref_s), rtol=1e-6)
    _require(
        _max_diff(back, ref_back) <= float(jnp.max(ref_s)) * 1.001,
        "int8 dequantize disagrees with q * scale",
    )
    _require(
        int(r_off.max()) <= 1 and float((r_off > 0).mean()) < 1e-3,
        "int8 reduce disagrees with _quant_math",
    )
    np.testing.assert_allclose(np.asarray(rs), np.asarray(ref_rs), rtol=1e-5)

    fp8 = "interpret mode, not asked" if dry_run else pq.pallas_verdict(pq.FP8)
    memory = dev.memory_stats() or {}
    _say(
        "B",
        platform=dev.platform,
        device_kind=repr(dev.device_kind),
        devices=len(jax.devices()),
        int8_verdict="not asked" if dry_run else repr(pq.pallas_verdict(pq.INT8)),
        fp8_lowers=(fp8 is None),
        fp8_verdict=repr(fp8),
        peak_bytes=memory.get("peak_bytes_in_use"),
    )


# --------------------------------------------------------------------------
# leg C: two replica groups in this process, a kill and a live heal
# --------------------------------------------------------------------------


class _Killed(Exception):
    pass


def leg_c(dry_run: bool, devices: Optional[List[Any]] = None) -> None:
    import jax
    import numpy as np
    import optax

    from torchft_tpu import native, tier as tier_mod
    from torchft_tpu.manager import Manager
    from torchft_tpu.models.llama import Llama, llama3_8b, llama_debug
    from torchft_tpu.ops import pallas_quant as pq
    from torchft_tpu.parallel.hsdp import HSDPTrainer, fsdp_shardings
    from torchft_tpu.parallel.mesh import make_mesh

    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    per = max(1, n // 2)
    shared = n < 2
    groups = [devices, devices] if shared else [devices[:per], devices[per : 2 * per]]
    published = llama3_8b()
    if dry_run:
        config, seq, cut = llama_debug(), 128, "toy"
    else:
        seq = 2048
        # depth is already one layer, so two replicas on ONE chip cut the
        # vocabulary (embedding + head are 1,051 M of the 1,269 M
        # parameters).  An eighth: at a quarter the first step, where both
        # hold gradients while one also receives the other's state, peaked
        # at 15.4 of the chip's 16 GB.
        vocab = published.vocab_size // 8 if shared else published.vocab_size
        config = dataclasses.replace(published, n_layers=1, vocab_size=vocab)
        cut = f"n_layers={published.n_layers}->1 vocab={published.vocab_size}->{vocab}"
    timeout_s = 20.0 if dry_run else 300.0

    tier = tier_mod.default_tier()
    _require(
        tier == "cpp" and tier_mod.data_plane_tier() == "cpp",
        f"native tier unavailable ({native.load_error()}); a failed build or "
        "load is an error here, not the Python tier",
    )
    lighthouse = tier_mod.make_lighthouse(
        bind="127.0.0.1:0",
        min_replicas=1,
        join_timeout_ms=200,
        quorum_tick_ms=20,
        # a 1 GB np.asarray holds the interpreter lock for seconds
        heartbeat_timeout_ms=1000 if dry_run else 10_000,
        tier=tier,
    )
    managers: List[Manager] = []
    rejoined = threading.Event()
    records: List[List[Dict[str, Any]]] = [[], []]
    digests: List[Dict[str, str]] = [{}, {}]
    device_sets: List[set] = [set(), set()]
    errors: List[BaseException] = []

    def digest(params: Any) -> str:
        """sha256 of every parameter's bytes, taken on the host: replicas
        that applied the same averaged gradients are bit-identical."""
        sha = hashlib.sha256()
        for leaf in jax.tree_util.tree_leaves(params):
            host = np.ascontiguousarray(np.asarray(leaf))
            sha.update(host.reshape(-1).view(np.uint8))
        return sha.hexdigest()[:16]

    def replica(idx: int) -> None:
        group = groups[idx]
        mesh = make_mesh(fsdp=len(group), devices=group)
        model = Llama(config)
        batch_sh = fsdp_shardings(model, mesh)[1]
        # a batch of its own: equal parameters at the end then REQUIRE the
        # replica-dimension average to have run
        tokens = (
            np.random.default_rng(idx)
            .integers(0, config.vocab_size, size=(len(group), seq))
            .astype(np.int32)
        )
        batch = tuple(
            jax.device_put(b, sh)
            for b, sh in zip((tokens, np.roll(tokens, -1, axis=1)), batch_sh)
        )
        life = 0
        while True:
            manager = Manager(
                comm=tier_mod.make_communicator(timeout_s=timeout_s),
                load_state_dict=None,
                state_dict=None,
                min_replica_size=1,
                timeout=timeout_s,
                quorum_timeout=timeout_s,
                connect_timeout=timeout_s,
                replica_id=f"smoke_{idx}",
                lighthouse_addr=lighthouse.local_address(),
                server_cls=tier_mod.manager_server_cls(tier),
            )
            managers.append(manager)
            trainer = HSDPTrainer(
                model,
                optax.adamw(1e-3),
                mesh,
                manager,
                # a restarted replica comes up with OTHER weights: only the
                # heal can make it equal to the survivor again
                key=jax.random.PRNGKey(life),
            )
            if life:
                _say("C", replica=idx, event="restarted")
                rejoined.set()
            try:
                stalled = 0
                while (step := manager.current_step()) < TOTAL_STEPS:
                    if life == 0 and step == KILL_AT:
                        digests[idx]["before_kill"] = digest(
                            trainer.holder["params"]
                        )
                    if life == 0 and idx == VICTIM and step >= KILL_AT:
                        raise _Killed()
                    if idx != VICTIM and step == KILL_AT + 2:
                        # do not finish before the restarted peer is back
                        _require(
                            rejoined.wait(timeout=timeout_s),
                            "the killed replica never came back",
                        )
                    # the int8 wire only where both replicas are known to be
                    # at the same step: a replica that restarts counts from 0
                    trainer.quantize_outer = PLAIN_STEPS <= step < KILL_AT
                    t0 = time.perf_counter()
                    loss, committed = trainer.train_step(batch)
                    jax.block_until_ready(trainer.holder["params"])
                    # a healing replica enters at 0 and leaves at the fleet's step
                    step = manager.current_step() - (1 if committed else 0)
                    records[idx].append(
                        dict(
                            step=step,
                            life=life,
                            committed=committed,
                            loss=round(loss, 4),
                            quantized=trainer.quantize_outer,
                            participants=manager.num_participants(),
                            seconds=round(time.perf_counter() - t0, 3),
                        )
                    )
                    _say("C", replica=idx, **records[idx][-1])
                    _require(np.isfinite(loss), f"replica {idx}: loss {loss}")
                    stalled = 0 if committed else stalled + 1
                    # before the kill nothing may fail; around it a step or
                    # two may be voted down, never a run of them
                    _require(
                        committed or (step >= KILL_AT and stalled < 3),
                        f"replica {idx}: step {step} did not commit; "
                        f"manager.errored() = {manager.errored()}",
                    )
                leaves = jax.tree_util.tree_leaves(trainer.holder)
                for leaf in leaves:
                    device_sets[idx] |= set(leaf.devices())
                digests[idx]["final"] = digest(trainer.holder["params"])
                _require(
                    manager.errored() is None,
                    f"replica {idx}: manager.errored() = {manager.errored()}",
                )
                return
            except _Killed:
                # said before the restart: on four chips the runtime has so
                # far ended the process right here, without a word
                # (CHANGES.md, PR 21)
                _say("C", replica=idx, event="killed, restarting")
                life += 1
                manager.shutdown()
                # the dead life's weights go with it (two lives of them do
                # not fit): the Manager's state hooks are what still reach them
                managers.remove(manager)
                del manager, trainer

    def guarded(idx: int) -> None:
        try:
            # what this thread creates without naming a device (its PRNG
            # key) lands on its own first chip, not on replica 0's
            with jax.default_device(groups[idx][0]):
                replica(idx)
        except BaseException as e:  # noqa: BLE001 — reported by the main thread
            errors.append(e)
            rejoined.set()  # never leave the peer parked on the gate

    threads = [
        threading.Thread(target=guarded, args=(i,), name=f"smoke_replica_{i}", daemon=True)
        for i in range(2)
    ]
    t0 = time.perf_counter()
    try:
        for t in threads:
            t.start()
        # a replica that failed leaves its peer inside a collective: stop
        # waiting at the first error, the teardown below aborts the rest
        while any(t.is_alive() for t in threads) and not errors:
            time.sleep(0.1)
    finally:
        for m in managers:
            try:
                m.shutdown()
            except Exception:  # noqa: BLE001 — best-effort teardown
                pass
        lighthouse.shutdown()
    if errors:
        if not isinstance(errors[0], LegFailed):
            traceback.print_exception(errors[0])
        raise LegFailed(f"{type(errors[0]).__name__}: {errors[0]}")

    # fault-free steps: every attempt before the kill committed, on both
    for idx, recs in enumerate(records):
        early = [r for r in recs if r["step"] < KILL_AT and r["life"] == 0]
        _require(
            len(early) == KILL_AT and all(r["committed"] for r in early),
            f"replica {idx}: fault-free steps {[(r['step'], r['committed']) for r in early]}",
        )
        _require(
            sum(r["quantized"] for r in early) == KILL_AT - PLAIN_STEPS,
            f"replica {idx} did not run the int8 wire",
        )
        tail = [r for r in recs if r["step"] >= KILL_AT + 2]
        _require(
            len(tail) >= 2 and all(r["committed"] and r["participants"] == 2 for r in tail),
            f"replica {idx}: steps after the heal {tail}",
        )
    _require(
        any(r["life"] == 1 for r in records[VICTIM]),
        "the kill was never injected",
    )
    # the replica-dimension average really ran (distinct batches, equal
    # parameters), and the healed replica is equal to the survivor again
    for when in ("before_kill", "final"):
        _require(
            digests[0][when] == digests[1][when],
            f"parameters differ across replicas {when}: {digests[0][when]} "
            f"vs {digests[1][when]}",
        )
    # every array of replica i lives only on replica i's devices
    for idx in range(2):
        _require(
            device_sets[idx] == set(groups[idx]),
            f"replica {idx} arrays on {device_sets[idx]}, its devices are {groups[idx]}",
        )
    if not shared:
        _require(
            not (device_sets[0] & device_sets[1]),
            f"replicas share devices: {device_sets[0] & device_sets[1]}",
        )

    # the survivor's second step on each wire (the first carries compiles)
    seconds = {r["step"]: r["seconds"] for r in records[1 - VICTIM]}
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use") or 0 for d in devices)
    _say(
        "C",
        platform=devices[0].platform,
        device_kind=repr(devices[0].device_kind),
        devices=n,
        layout=(
            "2 replicas share chip 0"
            if shared
            else f"2 replicas x {per} chips (fsdp={per}), disjoint"
        ),
        cut=repr(cut),
        params_M=round(Llama(config).num_params() / 1e6, 1),
        tier=tier,
        committed=[
            f"{sum(r['committed'] for r in recs)}/{len(recs)}" for recs in records
        ],
        healed=True,
        params_sha=digests[0]["final"],
        int8_kernels="jnp (not a TPU backend)"
        if dry_run
        else repr(pq.pallas_verdict(pq.INT8)),
        step_s_plain=seconds[PLAIN_STEPS - 1],
        step_s_int8=seconds[KILL_AT - 1],
        peak_bytes=peak or None,
        wall_s=round(time.perf_counter() - t0, 1),
    )


# --------------------------------------------------------------------------


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument(
        "--dry-run",
        action="store_true",
        help="CPU, tiny sizes, interpret mode: for developing this script "
        "and for the test suite; never prints the pass line",
    )
    dry_run = parser.parse_args().dry_run
    if dry_run:
        _enter_dry_run()
    # fails here, before anything is started, in a directory without the repo
    from torchft_tpu.utils.compile_cache import configure_compile_cache

    t0 = time.perf_counter()
    leg = "probe"
    try:
        with _deadline("the whole run", TOTAL_BOUND_S):
            device = probe_device()
            if device["platform"] != "tpu" and not dry_run:
                print(
                    f"chip_smoke: JAX found no TPU (it reports {device}); "
                    "nothing was run. --dry-run exercises the script on the "
                    "CPU.",
                    file=sys.stderr,
                )
                return 1
            leg = "A"
            leg_a(device, dry_run)
            # leg A's child has exited: now this process may hold the chip
            configure_compile_cache()
            leg = "B"
            with _deadline("leg B", LEG_B_BOUND_S):
                leg_b(dry_run)
            leg = "C"
            with _deadline("leg C", LEG_C_BOUND_S):
                leg_c(dry_run)
            import jax

            seen = jax.devices()
            device = {
                "platform": seen[0].platform,
                "kind": seen[0].device_kind,
                "count": len(seen),
            }
    except Exception as e:  # noqa: BLE001 — any failure fails the smoke
        if not isinstance(e, LegFailed):
            traceback.print_exc()
        print(f"FAIL leg {leg}: {e}", file=sys.stderr, flush=True)
        # a replica thread may still be parked inside a collective
        os._exit(1)
    print(f"all legs held in {time.perf_counter() - t0:.0f} s", flush=True)
    if dry_run:
        print(f"dry_run=true platform={device['platform']}", flush=True)
    else:
        print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
