"""The comparisons of a ``sambay`` cell that the harness does not make, on the
chip, at the published widths and a sequence that holds a float32 backward
pass:

    chiprun -- python3 ftbench/tests/sambay_forward_check.py --seeds 3
    chiprun -- python3 ftbench/tests/sambay_forward_check.py --seeds 3 --workload <cell>

``harness.forward_passes`` compares the cross-entropy of every position at the
cell's sequence length, forward only.  For every seed this takes weights and ONE
batch of ``--seq`` positions (2,048: four windows of 512) from the seed as a run
does (``harness.key_int(seed, 8888)`` and ``7777``) and

- **forward**: the LOGITS of every position, by the program (``model.apply``,
  its kernels in bfloat16), by the plain reference in float32 at ``highest``
  (``sambay_reference.logits``) and by the program on ``harness.coarse_copy``
  of the weights; held by the run's own rule and the architecture's ``K`` on
  the logits themselves (``rms(program - reference) <= rms(coarse - reference)
  / K``), and the cross-entropies by ``harness.reference_verdict``;
- **backward**: the gradient of ``model.loss`` with respect to one leaf of each
  kind (``W_x`` and ``A_log`` of a scan, ``l_q1`` of a windowed attention,
  ``W_1`` of the memory unit, the tied embedding), relative error (Frobenius)
  against ``jax.grad`` of the float32 reference, three ways.  **As a step
  computes it** (bfloat16, the compiled ``selscan_bwd`` and the flash backward
  at 64/128): held to ``STEP_LIMIT``, which lies between the largest reading of
  the sound program and the reading of the CONTROL, the same program on
  ``harness.coarse_copy`` of the weights; a control that reads under the limit
  fails the script too, since the limit then separates nothing.  **The same
  program in float32** (``torch_dtype`` float32 and products at ``highest``,
  the SAME Mosaic kernels on the chip): held to ``FLOAT32_LIMIT``, which is what
  tells a wrong backward kernel from bfloat16's rounding, and is the one rule
  ``l_q1`` is held by: that leaf's gradient is ONE number, ``d loss / d
  lambda``, times a fixed vector, a sum over every position that nearly
  cancels, so in bfloat16 its relative error is rounding over whatever the sum
  happens to leave on a seed (PERF.md section 6, PR 63).  Beside them the error
  of the reference's own gradient rounded to the leaf's dtype (the floor a
  bfloat16 leaf can reach).

Prints a line a seed and a part, and exits 1 if a comparison of either part
fails.  The benchmark's own runs never run this; PERF.md section 6 has its
readings.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

CELL = "phi4miniflash-ws1-seq16k"
# the backward part's limits on a leaf's relative error, set from two readings
# each (PERF.md section 6, PR 63, ``chiprun_out/pr63/fwd2.out`` and
# ``fin/fwd3.out``: six seeds at 2,048 positions on the chip, an eighth of the
# vocabulary; the limits were set after the first three and held the next
# three).  The step's gradient read 2.51 to 3.87 % on the four leaves it is
# held on (2.7 to 3.8 % at a quarter of the vocabulary) and its CONTROL, the
# same program on the e4m3 copy of the weights, 27.1 to 42.3 %: STEP_LIMIT,
# the geometric mean of the first three seeds' 3.43 and 32.5, keeps 2.7 times
# of room under it and 2.6 over it.  The program in float32 read 3.4e-6 to
# 1.07e-4 on all five leaves, where the step's least reading of any leaf is
# 1.06 % (``l_q1``, which also read 1.2, 1.3, 10.6, 11.7 and 19.5 %: rounding
# over a gradient norm of 0.007 to 0.034): FLOAT32_LIMIT keeps nine times of
# room under it and eleven over it
READ_STEP_LOW, READ_STEP_HIGH, READ_CONTROL_LOW, READ_FLOAT32_HIGH = 0.0106, 0.0387, 0.271, 1.07e-4
STEP_LIMIT = 0.105
FLOAT32_LIMIT = 1e-3
# held in bfloat16 too: every leaf but the lambda vector (the docstring says why)
STEP_HELD = ("w_x", "A_log", "w_1", "embed")
GRAD_LEAVES = (
    ("first", "M", "mixer", "w_x"), ("first", "M", "mixer", "A_log"), ("first", "S", "mixer", "lambda", "q1"),
    ("second", "G", "mixer", "w_1"), ("embed",),
)


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
    parser.add_argument("--seq", type=int, default=2048)
    parser.add_argument("--first-seed", type=int, default=2147486301, help="the first seed; the next lie seven apart")
    parser.add_argument("--rehearse", action="store_true",
                        help="toy widths in float32 on whatever backend there is: no coarse copy, the errors alone")
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
    config, arch, seq = dict(cell.config), cell.architecture, args.seq
    if args.rehearse:
        config.update(arch.TOY["config"])
        seq = arch.TOY["seq_len"]
    reference = arch.reference
    group = jax.devices()[: config["layout"]["chips_per_group"]]
    rows = len(group) * cell.traffic["sequences_per_chip"]
    mesh = make_mesh(fsdp=len(group), devices=group)
    model = arch.model(config)
    params_sh, batch_sh = fsdp_shardings(model, mesh)
    init = jax.jit(model.init, out_shardings=params_sh)
    forward = jax.jit(lambda p, b: (model.apply(p, b[0]), model.loss(p, b)))
    # the reference's layers rematerialised: its gradient keeps a layer's input, not its scores
    plain_layer = reference.layer
    reference.layer = lambda x, w, mixer, cfg: jax.checkpoint(lambda x, w: plain_layer(x, w, mixer, cfg))(x, w)

    def with_leaves(own, rest):
        for path, leaf in zip(GRAD_LEAVES, own):
            rest = _with(rest, path, leaf)
        return rest

    grad_as_a_step = jax.jit(jax.grad(lambda own, rest, b: model.loss(with_leaves(own, rest), b)))
    model32 = arch.model(dict(config, torch_dtype="float32"))
    with jax.default_matmul_precision("highest"):
        grad_in_float32 = jax.jit(jax.grad(lambda own, rest, b: model32.loss(with_leaves(own, rest), b)))
    grad_reference = jax.jit(jax.grad(lambda own, rest, tokens, targets: reference.loss(with_leaves(own, rest), (tokens, targets), config)))
    reference_logits = jax.jit(lambda p, tokens: reference.logits(p, tokens, config))
    def nll(logits, targets):
        logp = jax.nn.log_softmax(jnp.asarray(logits), axis=-1)
        return np.asarray(-jnp.take_along_axis(logp, jnp.asarray(targets)[..., None], axis=-1)[..., 0])

    rms = lambda a, b: float(np.sqrt(np.mean((np.asarray(a, np.float64) - np.asarray(b, np.float64)) ** 2)))  # noqa: E731
    seeds = [args.first_seed + 7 * i for i in range(args.seeds)]
    print(json.dumps(dict(device=jax.devices()[0].device_kind, seeds=seeds, k=arch.COARSE_RATIO_K, seq=seq)), flush=True)
    failed = 0
    for seed in seeds:
        t0 = time.monotonic()
        tokens, targets, batch = harness.seeded_batch(harness.key_int(seed, 7777), arch.vocab(config), rows, seq, batch_sh)
        with mesh:
            params = init(jax.random.PRNGKey(harness.key_int(seed, 8888)))
            logits, loss = forward(params, batch)
            logits, coarse = np.asarray(logits), None
            if not args.rehearse:
                coarse = np.asarray(forward(harness.coarse_copy(params, params_sh), batch)[0])
        host = jax.tree_util.tree_map(np.asarray, params)
        with jax.default_device(group[0]):
            f32 = jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), host)
            want = np.asarray(reference_logits(f32, tokens))
        verdict = harness.reference_verdict(
            nll(logits, targets), nll(want, targets), None if coarse is None else nll(coarse, targets), float(loss), arch.COARSE_RATIO_K
        )
        logits_rms = rms(logits, want)
        coarse_rms = None if coarse is None else rms(coarse, want)
        held = verdict["reference_arm"] is not None and (coarse is None or logits_rms <= coarse_rms / arch.COARSE_RATIO_K)
        failed += not held
        print(json.dumps(dict(
            seed=seed, part="forward", attention=model.attention_path, positions=int(logits.shape[0] * logits.shape[1]), held=held,
            logits_rms=logits_rms, coarse_logits_rms=coarse_rms, logits_ratio=None if coarse is None else coarse_rms / logits_rms,
            logits_abs_max=float(np.max(np.abs(want))), **verdict, seconds=round(time.monotonic() - t0, 1),
        )), flush=True)
        del logits, coarse, want

        t0 = time.monotonic()
        own = lambda tree: [_pick(tree, path) for path in GRAD_LEAVES]  # noqa: E731
        with mesh:
            got = [np.asarray(g, np.float32) for g in grad_as_a_step(own(params), params, batch)]
            control = None
            if not args.rehearse:
                coarse_params = harness.coarse_copy(params, params_sh)
                control = [np.asarray(g, np.float32) for g in grad_as_a_step(own(coarse_params), coarse_params, batch)]
                del coarse_params
        del params
        with jax.default_device(group[0]):
            wanted = [np.asarray(g) for g in grad_reference(own(f32), f32, tokens, targets)]
            with mesh, jax.default_matmul_precision("highest"):
                in_float32 = [np.asarray(g) for g in grad_in_float32(own(f32), f32, batch)]
        del f32
        for n, path in enumerate(GRAD_LEAVES):
            ref, dtype = wanted[n], _pick(host, path).dtype
            norm = float(np.linalg.norm(ref))
            error = lambda g: float(np.linalg.norm(g - ref)) / norm  # noqa: E731
            step, exact = error(got[n]), error(in_float32[n])
            coarse_error = None if control is None else error(control[n])
            held = None
            if not args.rehearse:
                held = exact <= FLOAT32_LIMIT
                if path[-1] in STEP_HELD:
                    held = held and step <= STEP_LIMIT < coarse_error
                failed += not held
            print(json.dumps(dict(
                seed=seed, part="backward", leaf="/".join(path), dtype=str(dtype), attention=model32.attention_path, held=held,
                reference_norm=norm, relative_error=step, relative_error_in_float32=exact, relative_error_on_the_coarse_copy=coarse_error,
                relative_error_of_the_reference_rounded=error(np.asarray(jnp.asarray(ref).astype(dtype).astype(jnp.float32))),
                step_limit=STEP_LIMIT if path[-1] in STEP_HELD else None, float32_limit=FLOAT32_LIMIT,
                seconds=round(time.monotonic() - t0, 1),
            )), flush=True)
        del host
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
