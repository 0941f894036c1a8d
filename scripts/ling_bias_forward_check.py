"""The benchmark's forward comparison (``ftbench/harness.py``:
``forward_passes`` and ``reference_verdict``) for a ``ling_hybrid`` cell with
the routers' selection BIAS drawn from the seed.  At ``model.init`` the bias
is zero, so a cell's own ``reference_agrees`` never routes by it; this does,
on the chip, at the published widths:

    chiprun -- python3 scripts/ling_bias_forward_check.py --seeds 3

Weights and batch as a cell makes them; every bias leaf is then set to
0.05 x normal (fifty of the bias's steps: enough to change which experts
many tokens take); the program in bfloat16, the program on the
float8_e4m3fn copy and the plain float32 reference all get the same biased
weights.  Prints one line a seed; exit code 1 if a seed does not agree.
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", default="ling3flash-ws1-seq8k")
    parser.add_argument("--seeds", type=int, default=3)
    args = parser.parse_args()

    import jax
    import numpy as np

    from ftbench import harness, spec
    from torchft_tpu.parallel.hsdp import fsdp_shardings
    from torchft_tpu.parallel.mesh import make_mesh
    from torchft_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    cell = spec.load_cell(args.workload)
    config, arch, seq = cell.config, cell.architecture, cell.traffic["seq_len"]
    group = jax.devices()[: config["layout"]["chips_per_group"]]
    rows = len(group) * cell.traffic["sequences_per_chip"]
    mesh = make_mesh(fsdp=len(group), devices=group)
    model = arch.model(config)
    params_sh, batch_sh = fsdp_shardings(model, mesh)
    nll_fn = jax.jit(lambda p, b: harness.system_token_nll(model, p, b))

    def biased(params, key):
        return jax.tree_util.tree_map(
            lambda p, is_state: 0.05 * jax.random.normal(key, p.shape, p.dtype) if is_state else p,
            params, model.state_mask(),
        )

    ok = True
    for n in range(args.seeds):
        seed = 2147485101 + n
        tokens, targets, batch = harness.seeded_batch(
            harness.key_int(seed, 7777), arch.vocab(config), rows, seq, batch_sh
        )
        with mesh:
            params = jax.jit(model.init, out_shardings=params_sh)(
                jax.random.PRNGKey(harness.key_int(seed, 8888))
            )
            params = jax.jit(biased, out_shardings=params_sh)(
                params, jax.random.PRNGKey(harness.key_int(seed, 9999))
            )
            system_loss = float(jax.jit(model.loss)(params, batch))
            system = np.asarray(nll_fn(params, batch))
            coarse = np.asarray(nll_fn(harness.coarse_copy(params, params_sh), batch))
        host = jax.tree_util.tree_map(np.asarray, params)
        del params
        with jax.default_device(group[0]):
            reference = np.asarray(arch.token_nll(host, tokens, targets, config))
        verdict = harness.reference_verdict(system, reference, coarse, system_loss, arch.COARSE_RATIO_K)
        ok = ok and verdict["reference_arm"] is not None
        print(json.dumps(dict(seed=seed, attention=model.attention_path, **verdict)), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
