"""The two readings ``harness.COARSE_RATIO_K`` is set from, read on the chip
in one process and with no window:

    chiprun -- python3 ftbench/tests/calibrate_forward_check.py --seeds 20

For every configuration of ``BENCHMARK.json`` and every seed, what a run of
a cell computes after its window (``harness.forward_passes``: weights and
batch from the seed alone) and, beside it, the CONTROL: the program on an
int8 copy of the weights with a scale a channel, the finest 8-bit path a
later PR could be tempted by, and for a few seeds the plain reference on
that copy (the weights' rounding alone, without bfloat16's).  Prints, per
seed, the ratio ``coarse_token_rms / token_rms`` of the sound program and of
each control judged in its place, and keeps every position's numbers in
``chiprun_out/pr25/forward_check/<configuration>.npz``.  The benchmark's own
runs never run this; ``test_ftbench_reference_rule.py`` holds the rule to
some of these readings.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

# ISSUE 25's seeds (PR 24's four, PR 23's six), then fresh ones
NAMED_SEEDS = [
    2147484211, 2147484101, 2147484102, 2147484103,
    2147483659, 2147483693, 2147483713, 2147483743, 2147483777, 2147483783,
]
OUT = os.path.join(ROOT, "chiprun_out", "pr25", "forward_check")
# the plain reference on the int8 copy costs a second pass through the host
REFERENCE_CONTROL_SEEDS = 4
KINDS = ["system", "reference", "coarse", "int8_channel", "reference_int8_channel"]


def int8_channel_copy(params, shardings=None):
    """Every bfloat16 matrix as symmetric int8 with a scale for each output
    channel (each column of a leaf's last two axes; the embedding: each
    row), and back.  ``jnp.round`` is an operation of its own, so this
    rounds inside one program."""
    import jax
    import jax.numpy as jnp

    def leaf(path, x):
        if x.dtype != jnp.bfloat16 or x.ndim < 2:
            return x
        axis = -1 if "embed" in jax.tree_util.keystr(path) else -2
        x32 = x.astype(jnp.float32)
        amax = jnp.max(jnp.abs(x32), axis=axis, keepdims=True)
        scale = jnp.where(amax > 0, amax / 127.0, 1.0)
        return (jnp.clip(jnp.round(x32 / scale), -127, 127) * scale).astype(x.dtype)

    return jax.jit(
        lambda p: jax.tree_util.tree_map_with_path(leaf, p), out_shardings=shardings
    )(params)


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seeds", type=int, default=20)
    args = parser.parse_args()

    import jax
    import numpy as np

    from ftbench import harness, reference
    from torchft_tpu.models.llama import Llama
    from torchft_tpu.parallel.hsdp import fsdp_shardings
    from torchft_tpu.parallel.mesh import make_mesh
    from torchft_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    os.makedirs(OUT, exist_ok=True)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    seeds = (NAMED_SEEDS + [2147484501 + i for i in range(args.seeds)])[: args.seeds]
    print(json.dumps(dict(device=jax.devices()[0].device_kind, seeds=seeds, k=harness.COARSE_RATIO_K)))
    for entry in bench["configs"]:
        with open(os.path.join(ROOT, entry["file"])) as f:
            config = json.load(f)
        seq = 2048
        group = jax.devices()[: config["layout"]["chips_per_group"]]
        mesh = make_mesh(fsdp=len(group), devices=group)
        model = Llama(harness.llama_config(config))
        kept = {}

        def int8_and_keep(params, shardings):
            # the same copy, also handed to the plain reference below
            kept["int8"] = int8_channel_copy(params, shardings)
            return kept["int8"]

        rows = []
        for n, seed in enumerate(seeds):
            t0 = time.monotonic()
            system_loss, nll = harness.forward_passes(
                model, mesh, config, seed, len(group), seq,
                {"coarse": harness.coarse_copy, "int8_channel": int8_and_keep},
            )
            int8 = kept.pop("int8")
            if n < REFERENCE_CONTROL_SEEDS:
                tokens, targets, _ = harness.seeded_batch(
                    harness.key_int(seed, 7777), config["vocab_size"], len(group), seq,
                    fsdp_shardings(model, mesh)[1],
                )
                host = jax.tree_util.tree_map(np.asarray, int8)
                with jax.default_device(group[0]):
                    nll["reference_int8_channel"] = np.asarray(
                        reference.token_nll(host, tokens, targets, harness.shapes_of(config))
                    )
            del int8
            sound = harness.reference_verdict(nll["system"], nll["reference"], nll["coarse"], system_loss)
            line = dict(
                config=entry["name"], seed=seed, attention=model.attention_path,
                seconds=round(time.monotonic() - t0, 1),
                **{k: sound.get(k) for k in (
                    "reference_arm", "system_loss", "reference_loss", "loss_tie", "token_rms",
                    "coarse_token_rms", "coarse_ratio")},
            )
            for control in ("coarse", "int8_channel", "reference_int8_channel"):
                if control in nll:
                    judged = harness.reference_verdict(
                        nll[control], nll["reference"], nll["coarse"], float(nll[control].mean())
                    )
                    line[control + "_as_system"] = dict(
                        arm=judged["reference_arm"], token_rms=judged["token_rms"],
                        ratio=judged.get("coarse_ratio"),
                    )
            print(json.dumps(line), flush=True)
            rows.append(np.stack([
                nll.get(k, np.full_like(nll["system"], np.nan)).ravel().astype(np.float32) for k in KINDS
            ]))
        np.savez_compressed(
            os.path.join(OUT, entry["name"] + ".npz"),
            nll=np.stack(rows), seeds=np.asarray(seeds, np.int64), kinds=np.asarray(KINDS),
        )
    return 0


if __name__ == "__main__":
    sys.exit(main())
