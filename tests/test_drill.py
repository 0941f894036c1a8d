"""Joint FT x SPMD kill/heal: real TCP replicas, real HSDP meshes.

The composition of a real DCN-tier
communicator with compiled mesh parallelism, including a whole-replica
death and live heal, validated in one run.
"""

import dataclasses

import jax
import pytest

from torchft_tpu.drill import joint_ft_spmd_drill
from torchft_tpu.models.llama import llama_debug


def test_joint_ft_spmd_kill_heal() -> None:
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 (virtual) devices")
    facts = joint_ft_spmd_drill(
        n_devices=8, num_replicas=2, num_steps=6, kill_replica=1, kill_at_step=2
    )
    assert facts["restarts"] == 1
    assert facts["healed"]


def test_joint_ft_spmd_quantized_outer_ring() -> None:
    """HSDP with the int8 outer ring (quantize_outer=True): every replica
    applies the identical requantized averaged stream, so sharded state
    stays bit-identical across replicas — the assertion inside the drill.
    The model and the heartbeat bound are the caller's here, as a run at a
    real width passes them."""
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 (virtual) devices")
    facts = joint_ft_spmd_drill(
        n_devices=8,
        num_replicas=2,
        num_steps=5,
        kill_replica=None,
        quantize_outer=True,
        config=dataclasses.replace(llama_debug(), n_layers=1, vocab_size=384),
        heartbeat_timeout_ms=3000,
    )
    assert facts["restarts"] == 0


@pytest.mark.slow
def test_joint_ft_spmd_striped_heal_with_source_kill() -> None:
    """3 replicas, one killed: the rejoiner heals STRIPED from the 2
    survivors while chaos kills one survivor's transport mid-transfer —
    the heal must complete from the remaining source and all replicas
    still converge bit-identically.

    Marked slow: the full 3-replica drill under churn occasionally trips a
    pre-existing per-group-commit divergence window (one replica's
    collective errors while another's completes, and commit votes are per
    replica group), independent of the striped heal itself — the
    deterministic mid-heal-failover coverage lives in
    tests/test_striped_heal.py."""
    if len(jax.devices()) < 6:
        pytest.skip("needs 6 (virtual) devices")
    facts = joint_ft_spmd_drill(
        n_devices=6,
        num_replicas=3,
        num_steps=6,
        kill_replica=1,
        kill_at_step=2,
        heal_source_chaos=True,
    )
    assert facts["restarts"] == 1
    assert facts["healed"]
    assert facts["heal_source_killed"]
    # the striped heal recorded its throughput facts
    assert facts["heal_timings"].get("heal_num_sources") == 2.0
    assert facts["heal_timings"].get("heal_bytes", 0) > 0
