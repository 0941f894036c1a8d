"""The comparisons of a ``looped`` cell that the harness cannot make, on the
chip, at the cell's widths:

    chiprun -- python3 ftbench/tests/loop_forward_check.py --seeds 3
    chiprun -- python3 ftbench/tests/loop_forward_check.py --seeds 3 --workload <cell>

``harness.forward_passes`` compares what ``model.apply`` gives, the LAST
pass's head; the earlier passes' heads and the exit gate are in what a step
differentiates and in nothing ``reference_agrees`` reads.  For every seed this
takes weights and ONE batch from the seed as a run does
(``harness.key_int(seed, 8888)`` and ``7777``) and

- **forward, at the cell's sequence length**: every pass's cross-entropy of
  every position and every position's exit distribution ``p``, three times: by
  the program (``model.pass_losses``, its kernels in bfloat16), by the plain
  reference in float32 at ``highest`` (``looped_reference.passes``), and by the
  program on ``harness.coarse_copy`` of the weights.  Each pass's two arrays
  are held to the run's own rule and the architecture's ``K``
  (``harness.reference_verdict``): ``token_rms <= coarse_token_rms / K``, and
  the tie is between the mean of the program's positions and the ``pass_nll``
  / ``exit_p`` that ``jit(model.objective)`` reports in the step's summary, so
  that what is compared IS what a step computes;
- **backward, at ``--grad-seq`` positions** (2,048): the gradient of the
  objective with respect to two stacked leaves (``wq``, ``w_down``) and the
  gate's vector, by the program as a step computes it (the passes'
  contributions added in the leaves' bfloat16 by the pass scan's
  transposition), by the program with every pass given leaves of its own and
  the ``T`` contributions added in float32 here, and by ``jax.grad`` of the
  float32 reference; the relative error (Frobenius) of the first two against
  the third, beside that of the reference's own gradient rounded to bfloat16
  (the floor a bfloat16 leaf can reach).  ``assumed.gradient_sum`` of the
  configuration is answered from these.

Prints a line a seed and a part, and exits 1 if any forward comparison fails.
The benchmark's own runs never run this; PERF.md section 6 has its readings.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

CELL = "ouro2.6b-ws1-seq16k"
GRAD_LEAVES = (("layers", "wq"), ("layers", "w_down"), ("gate", "w"))


def _pick(tree, path):
    for key in path:
        tree = tree[key]
    return tree


def _with(tree, path, value):
    """``tree`` with the leaf at ``path`` replaced (the dicts on the way copied)."""
    if not path:
        return value
    return {**tree, path[0]: _with(tree[path[0]], path[1:], value)}


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seeds", type=int, default=3)
    parser.add_argument("--workload", default=CELL)
    parser.add_argument("--grad-seq", type=int, default=2048)
    parser.add_argument("--rehearse", action="store_true",
                        help="toy widths in float32 on whatever backend there is: the absolute arm, no coarse copy")
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ftbench import harness, spec
    from torchft_tpu.parallel.hsdp import fsdp_shardings
    from torchft_tpu.parallel.mesh import make_mesh
    from torchft_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    cell = spec.load_cell(args.workload)
    config, arch, seq, grad_seq = dict(cell.config), cell.architecture, cell.traffic["seq_len"], args.grad_seq
    if args.rehearse:
        config.update(arch.TOY["config"])
        seq, grad_seq = arch.TOY["seq_len"], arch.TOY["seq_len"] // 2
    reference, passes = arch.reference, config["total_ut_steps"]
    group = jax.devices()[: config["layout"]["chips_per_group"]]
    rows = len(group) * cell.traffic["sequences_per_chip"]
    mesh = make_mesh(fsdp=len(group), devices=group)
    model = arch.model(config)
    params_sh, batch_sh = fsdp_shardings(model, mesh)
    init = jax.jit(model.init, out_shardings=params_sh)
    forward = jax.jit(model.pass_losses)
    summary = jax.jit(lambda p, b: model.objective(p, b)[1][1])
    # the reference's layers rematerialised: its gradient keeps a layer's input, not its scores
    plain_block = reference.block
    reference.block = lambda h, w, cfg: jax.checkpoint(lambda h, w: plain_block(h, w, cfg))(h, w)

    def objective_of(own, rest, b, layers_by_pass=None):
        """The program's objective with the leaves of ``GRAD_LEAVES`` taken from ``own``."""
        for path, leaf in zip(GRAD_LEAVES, own):
            rest = _with(rest, path, leaf)
        return model.objective(rest, b, layers_by_pass)[0]

    grad_as_a_step = jax.jit(jax.grad(objective_of))
    grad_by_pass = jax.jit(jax.grad(lambda own, layers, rest, b: objective_of(own, rest, b, layers), argnums=(0, 1)))

    def reference_objective(own, rest, tokens, targets):
        for path, leaf in zip(GRAD_LEAVES, own):
            rest = _with(rest, path, leaf)
        return reference.objective(rest, (tokens, targets), config)

    grad_reference = jax.jit(jax.grad(reference_objective))
    seeds = [2147485901 + 7 * i for i in range(args.seeds)]
    print(json.dumps(dict(device=jax.devices()[0].device_kind, seeds=seeds, k=arch.COARSE_RATIO_K, seq=seq, grad_seq=grad_seq)), flush=True)
    failed = 0
    for seed in seeds:
        t0 = time.monotonic()
        tokens, targets, batch = harness.seeded_batch(harness.key_int(seed, 7777), arch.vocab(config), rows, seq, batch_sh)
        with mesh:
            params = init(jax.random.PRNGKey(harness.key_int(seed, 8888)))
            nll, log_p = (np.asarray(a) for a in forward(params, batch))
            reported = model.summary_stats(np.asarray(summary(params, batch)))
            coarse = None
            if not args.rehearse:
                coarse = [np.asarray(a) for a in forward(harness.coarse_copy(params, params_sh), batch)]
        host = jax.tree_util.tree_map(np.asarray, params)
        with jax.default_device(group[0]):
            want = {k: np.asarray(v) for k, v in reference.passes(host, (tokens, targets), config).items()}
        held = []
        for t in range(passes):
            for name, got, ref, got_coarse, said in (
                ("nll", nll[t], want["nll"][t], None if coarse is None else coarse[0][t], reported["pass_nll"][t]),
                ("p", np.exp(log_p[t]), want["p"][t], None if coarse is None else np.exp(coarse[1][t]), reported["exit_p"][t]),
            ):
                verdict = harness.reference_verdict(got, ref, got_coarse, said, arch.COARSE_RATIO_K)
                held.append(verdict["reference_arm"] is not None)
                print(json.dumps(dict(seed=seed, part="forward", of=name, pass_=t + 1, **verdict)), flush=True)
        failed += not all(held)
        print(json.dumps(dict(
            seed=seed, part="forward", attention=model.attention_path, positions=int(nll[0].size), held=all(held),
            objective_reference=float(want["objective"]), **{name + "_reported": value for name, value in reported.items()},
            seconds=round(time.monotonic() - t0, 1),
        )), flush=True)

        # -- backward, at a shorter sequence ---------------------------------
        t0 = time.monotonic()
        tokens, targets, batch = harness.seeded_batch(harness.key_int(seed, 7777), arch.vocab(config), rows, grad_seq, batch_sh)
        picked = [_pick(params, path) for path in GRAD_LEAVES]
        tiled = jax.tree_util.tree_map(lambda a: jnp.broadcast_to(a, (passes, *a.shape)), params["layers"])
        with mesh:
            as_a_step = grad_as_a_step(picked, params, batch)
            by_pass = grad_by_pass(picked, tiled, params, batch)
        got = [np.asarray(g, np.float32) for g in as_a_step]
        # every pass's contribution to a stacked leaf alone, added in float32; the gate's is not a pass's leaf
        summed = [
            np.asarray(jnp.sum(_pick({"layers": by_pass[1]}, path).astype(jnp.float32), axis=0)) if path[0] == "layers"
            else np.asarray(own, np.float32)
            for path, own in zip(GRAD_LEAVES, by_pass[0])
        ]
        del as_a_step, by_pass, tiled, params, picked
        with jax.default_device(group[0]):
            f32 = jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), host)
            wanted = [np.asarray(g) for g in grad_reference([_pick(f32, path) for path in GRAD_LEAVES], f32, tokens, targets)]
        del f32
        for n, path in enumerate(GRAD_LEAVES):
            ref, dtype = wanted[n], _pick(host, path).dtype
            norm = float(np.linalg.norm(ref))
            error = lambda g: float(np.linalg.norm(g - ref)) / norm  # noqa: E731
            print(json.dumps(dict(
                seed=seed, part="backward", leaf="/".join(path), dtype=str(dtype), reference_norm=norm,
                relative_error_summed_as_a_step=error(got[n]), relative_error_summed_in_float32=error(summed[n]),
                relative_error_of_the_reference_rounded=error(np.asarray(jnp.asarray(ref).astype(dtype).astype(jnp.float32))),
                seconds=round(time.monotonic() - t0, 1),
            )), flush=True)
        del host
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
