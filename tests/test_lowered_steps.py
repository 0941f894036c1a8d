"""The gradient steps of the models that share ``RoutedExperts`` and
``flash_attention`` lower, at toy widths and on both paths (plain, and the
kernels in interpret mode), to the bytes they lowered to (but for the
counters jax appends to the names of private functions) before a fourth
model's expert form and kept names went in (PR 35: the digests under
``tests/fixtures/lowered_steps.json`` were written by THIS file run on the
parent commit), and ``llama``'s and ``ssm_hybrid_moe``'s to what they lowered
to before ``flash_attention`` took a window (PR 41: theirs written the same
way, on PR 41's parent; ``windowed_moe``'s is its own first tree's).  PR 42
wrote the four expert models' eight digests anew (``--write --only``: their
step's summary gained a column, ``buffer_rows``, and the experts' rows go
through their buffer in a loop of passes); ``llama``'s two were PR 41's
parent's still.  PR 50 wrote the ``kernels`` digests of the models that call
flash anew (``--write --only ling_hybrid,llama,ssm_hybrid_moe,windowed_moe``:
the kernels' grids hold the live blocks alone and read their walk from
tables, ``ops/flash_attention.py``); the five ``plain`` digests and both of
``indexed_sparse_moe`` did NOT change, which is the proof that the plain path
and Keye's kernels were not touched.  (``latent_moe`` is not here: its digest
came out another in a worker that had traced other files first.)  PR 52 ADDED
``eva``'s two (``--write --only eva``: ``flash_attention`` took a second rule
of liveness, two key sources under one softmax, and ``DEVICE_PARTS`` a
twelfth part) and changed none of the ten.  PR 53 wrote ONE anew
(``--write --only indexed_sparse_moe``, whose ``plain`` digest came out the
same: Keye's ``dsa_attn_*`` and ``dsa_probs`` launches walk their live blocks
from ``flash_attention``'s tables) and no other changed: the flash models'
steps are what they were.  PR 56 ADDED ``gated_delta_moe``'s two (``--write
--only gated_delta_moe``: ``RoutedExperts`` took a gate on the shared expert,
``ops/gdn.py`` imports ``ops/kda.py``'s inverse) and changed none of the
twelve: no other model's program moved.  PR 59 ADDED ``looped``'s two (``--write
--only looped``: ``DEVICE_PARTS`` took a thirteenth part, ``loop_gate``; no
helper of ``Llama`` and no kernel was touched) and changed none of the
fourteen.  PR 62 wrote the ``kernels`` digests of the models whose flash
residual changed anew (``--write --only
ling_hybrid,llama,ssm_hybrid_moe,windowed_moe,gated_delta_moe,looped``: the
forward rule keeps its row statistics as ONE float32 a row, ``[B, H, S]``, and
spreads them again in the backward rule; ``looped``'s layer keeps what flash
made); the eight ``plain`` digests and both of ``indexed_sparse_moe`` did NOT
change, nor ``eva``'s ``kernels`` one: its rule was already this one, and the
``custom_vjp``'s Python name (``_pooled_hm`` then, ``_flash_hm`` now) is not
in the lowered text.  PR 64 wrote the ``kernels`` digests of the eight models
that call flash anew (``--write --only
ling_hybrid,llama,ssm_hybrid_moe,windowed_moe,eva,gated_delta_moe,looped,sambay``:
the forward's score tile lies keys-major and its second output is ``[B, H,
S]``, ``ops/flash_attention.py``); the nine ``plain`` digests and both of
``indexed_sparse_moe`` did NOT change, which is the proof that the plain path
and Keye's kernels were not touched.  Since PR 64 the text is made from jax's
caches as a new process has them (``tests/_toys.py`` ``_lowered_grad_step``
clears them before it lowers): jax shares a private function between two
places of the text where its caches hand both the same jaxpr object, so
``ssm_hybrid_moe``'s two digests, whose runs share ONE policy object, came out
another in a worker that had traced other files first.  PR 65 wrote ONE anew
(``--write --only indexed_sparse_moe``, whose ``plain`` digest came out the
same: Keye's ``dsa_attn_fwd`` takes the bits keys-major and its tile lies
keys-major, its second output is ``[B, H, S]``, ``ops/indexed_attention.py``)
and none of the other seventeen changed.  PR 67 ADDED
``prerouted_moe``'s two (``--write --only prerouted_moe``: ``RoutedExperts.apply``
took ``route_from`` and ``expert_form`` a third value, "reglu") and changed none
of the eighteen: with ``route_from`` None the six expert models' programs are
the parent's to the letter.  PR 68 wrote ONE anew (``--write --only
indexed_sparse_moe``, whose ``plain`` digest came out the same: Keye's
attention over the picked keys takes its backward in one launch,
``dsa_attn_dkv``, where it took ``dsa_attn_dq`` and ``dsa_attn_dkv``,
``ops/indexed_attention.py``) and none of the other nineteen changed: the nine
other models' steps are what they were.  PR 69 ADDED ``ssm_hybrid_dense``'s two
(``--write --only ssm_hybrid_dense``: ``ops/ssd.py`` walks a group wider than
``HEAD_BLOCK`` in head blocks, ``DEVICE_PARTS`` took ``mixer_conv`` and
``mixer_gate``) and changed none of the twenty: a group of ONE block, which
``ssm_hybrid_moe``'s toy and cell are, runs the program it ran, to the letter.  A later change that
means to alter one of these programs writes the fixture anew and says so:
``python tests/test_lowered_steps.py --write``."""

import hashlib
import json
import os
import re
import sys

import pytest

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures", "lowered_steps.json")
MODELS = ("ling_hybrid", "indexed_sparse_moe", "llama", "ssm_hybrid_moe", "windowed_moe", "eva", "gated_delta_moe", "looped", "sambay", "prerouted_moe", "ssm_hybrid_dense")
CASES = [(m, p) for m in MODELS for p in ("plain", "kernels")]


def digest(name: str, path: str) -> str:
    from tests._toys import step_texts  # traced once a run for this file and ``test_device_parts.py``

    text = step_texts(name, path)["lowered"]
    # jax numbers its private functions (@silu_808) from one counter a
    # process: the numbers say what else was traced, not what the program is
    text = re.sub(r"@([A-Za-z_]\w*?)_\d+\b", r"@\1", text)
    return hashlib.sha256(text.encode()).hexdigest()


@pytest.mark.parametrize("name,path", CASES)
def test_lowers_to_the_bytes_it_lowered_to(name, path):
    with open(FIXTURE) as f:
        want = json.load(f)
    if want["jax"] != __import__("jax").__version__:
        pytest.skip(f"the digests were written under jax {want['jax']}")
    assert digest(name, path) == want["sha256"][f"{name}:{path}"], (
        f"{name}'s gradient step ({path}) lowers to another program than the fixture's: if that is "
        "meant, write the fixture anew (python tests/test_lowered_steps.py --write) and say so"
    )


if __name__ == "__main__" and "--write" in sys.argv:
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    import tests.conftest  # noqa: F401  (the tests' environment: the CPU's eight devices)

    import jax

    # ``--only a,b`` writes those models' digests anew and keeps the others'
    only = sys.argv[sys.argv.index("--only") + 1].split(",") if "--only" in sys.argv else MODELS
    with open(FIXTURE) as f:
        kept = json.load(f)["sha256"]
    out = {"jax": jax.__version__, "sha256": {**kept, **{f"{n}:{p}": digest(n, p) for n, p in CASES if n in only}}}
    with open(FIXTURE, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out, indent=1))
