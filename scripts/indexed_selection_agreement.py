"""What share of the keys the program's index picks the float32 reference
also picks, layer by layer, on the chip at a cell's own size:

    chiprun -- python3 scripts/indexed_selection_agreement.py --seeds 2

The selection makes a layer's output a step function of the index's scores:
two computations of the same scores in different precisions pick key sets
that differ where scores lie close, and the attention then reads other
keys.  Weights and batch as a cell's forward comparison makes them
(``ftbench/harness.py``).  The reference walks its own residual stream in
float32; at every layer the PROGRAM's index (its projections, rope and
``select_keys`` in the model's dtype) is given that same stream, so the
share read is the index's precision alone and not what the layers before it
drifted.  Prints one line a seed; the last line is ``AGREEMENT {...}``.
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", default="keye2-ws1-seq16k")
    parser.add_argument("--seeds", type=int, default=2)
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ftbench import harness, spec
    from torchft_tpu.models.indexed_sparse_moe import text_positions
    from torchft_tpu.models.llama import Llama
    from torchft_tpu.ops.indexed_attention import select_keys
    from torchft_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    cell = spec.load_cell(args.workload)
    config, arch, seq = cell.config, cell.architecture, cell.traffic["seq_len"]
    reference = arch.reference
    model = arch.model(config)
    cfg = model.config
    interpret = jax.default_backend() != "tpu"

    @jax.jit
    def program_bits_at(x, w, at):
        """Whether the program's index, given the residual stream ``x``,
        picked each position of ``at`` [B, S, topk]."""
        B, S, _ = x.shape
        h = Llama._rms_norm(x.astype(cfg.dtype), w["attn_norm"], cfg.norm_eps)
        mask, _, keys = select_keys(
            *model._index(h, w["index"], text_positions(B, S)), topk=cfg.index_topk, blocks=cfg.blocks,
            interpret=interpret,
        )
        bk = mask.shape[-1]
        block = at // bk
        words = mask[jnp.arange(B)[:, None, None], block // 32, jnp.arange(S)[None, :, None], at % bk]
        return (words >> (block % 32)) & 1, jnp.mean(keys)

    lines = []
    for n in range(args.seeds):
        seed = 2147486101 + n
        tokens = np.random.default_rng(harness.key_int(seed, 7777)).integers(
            0, arch.vocab(config), size=(1, seq)
        ).astype(np.int32)
        params = jax.jit(model.init)(jax.random.PRNGKey(harness.key_int(seed, 8888)))
        held = tuple(config["experts_held"])
        shares = []
        x = jnp.asarray(params["embed"], jnp.float32)[tokens]
        positions = jnp.broadcast_to(jnp.arange(seq), (3, 1, seq))
        for i in range(config["num_hidden_layers"]):
            layer = jax.tree_util.tree_map(lambda a: a[i], params["layers"])
            w32 = jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), layer)
            with jax.default_matmul_precision("highest"):  # the reference's alone: the kernels take bfloat16
                x_next, _, (at, counted), _, _ = reference.block(x, w32, positions, config, held)
            bits, keys = program_bits_at(x, layer, at)
            shares.append(float(jnp.sum(jnp.where(counted, bits, 0)) / jnp.sum(counted)))
            x = x_next
        line = dict(seed=seed, keys_per_query=float(keys), share_also_picked=shares)
        print(json.dumps(line), flush=True)
        lines.append(line)
    every = [s for line in lines for s in line["share_also_picked"]]
    print("AGREEMENT " + json.dumps(dict(
        workload=args.workload, device=jax.devices()[0].device_kind, seeds=len(lines),
        lowest=min(every), highest=max(every), mean=sum(every) / len(every),
    )))
    return 0


if __name__ == "__main__":
    sys.exit(main())
