"""Scale dress-rehearsal: the BASELINE pod configs must validate abstractly.

The reference's scale claim (Llama-3 8B/70B HSDP,
``/root/reference/README.md:62-69``) is only testable on a cluster; here the
XLA compilation model lets the real train step trace + SPMD-lower for the
real pod shape over an AbstractMesh with zero devices, so axis-divisibility
and HBM-fit surprises surface in CI instead of at bring-up.
"""

import optax
import pytest

from torchft_tpu.models.llama import Llama, llama3_8b, llama_debug
from torchft_tpu.parallel.rehearsal import baseline_reports, rehearse


class TestBaselineConfigs:
    @pytest.fixture(scope="class")
    def reports(self):
        return {r.name: r for r in baseline_reports(lower=True)}

    def test_all_baseline_configs_pass(self, reports):
        for name, r in reports.items():
            assert r.ok, f"{name}: {r.summary()}"

    def test_full_program_lowered_for_tpu(self, reports):
        for r in reports.values():
            assert r.lowered_grad and r.lowered_update, r.summary()

    def test_hbm_fit_with_margin(self, reports):
        for r in reports.values():
            assert r.hbm_frac < 0.8, r.summary()
            # and the accounting is non-trivial (not all zeros)
            assert r.bytes_per_device["total"] > 1e9

    def test_70b_is_the_biggest(self, reports):
        per_dev = {
            n: r.bytes_per_device["params"] for n, r in reports.items()
        }
        assert max(per_dev, key=per_dev.get).startswith("config5_70b")


class TestRehearsalCatchesBadConfigs:
    def test_divisibility_violation_detected(self):
        # 8B has 32 heads / 8 kv heads: tp=12 cannot divide the 4096-wide
        # q projection output (32 heads x 128) nor kv (8 x 128 = 1024)
        r = rehearse(
            Llama(llama3_8b()),
            optax.adamw(1e-3),
            {"dp": 1, "fsdp": 2, "tp": 12},
            batch=8,
            seq=8192,
            name="bad_tp",
            lower=False,
        )
        assert not r.ok
        assert r.divisibility_errors

    def test_batch_must_divide_data_axes(self):
        r = rehearse(
            Llama(llama_debug()),
            optax.adamw(1e-3),
            {"dp": 2, "fsdp": 2, "tp": 1},
            batch=6,  # 6 % (2*2) != 0
            seq=256,
            name="bad_batch",
            lower=False,
        )
        assert not r.ok
        assert any("batch" in e for e in r.divisibility_errors)

    def test_hbm_overflow_detected(self):
        # 8B replicated on ONE v5e chip (16 GB): cannot fit
        r = rehearse(
            Llama(llama3_8b()),
            optax.adamw(1e-3),
            {"dp": 1, "fsdp": 1, "tp": 1},
            batch=8,
            seq=8192,
            name="too_big",
            chip="v5e",
            lower=False,
        )
        assert not r.ok
        assert r.hbm_frac > 1.0

    def test_debug_model_lowers(self):
        r = rehearse(
            Llama(llama_debug()),
            optax.adamw(1e-3),
            {"dp": 2, "fsdp": 2, "tp": 2},
            batch=8,
            seq=256,
            name="debug",
            lower=True,
        )
        assert r.ok, r.summary()


class TestQuantKernelLowering:
    def test_all_quant_kernels_lower_for_tpu(self):
        """Round-4 verdict item 9: every device quant kernel (quantize /
        fused reduce / dequantize) x every wire kind must TPU-lower — a
        Mosaic-inexpressible program fails here in CI, not at cluster
        bring-up.  Per-generation compile still needs metal (covered at
        runtime by pallas_quant.pallas_verdict)."""
        from torchft_tpu.parallel.rehearsal import quant_kernel_reports

        rows = quant_kernel_reports()
        assert {(r["kernel"], r["kind"]) for r in rows} == {
            (k, w)
            for k in ("quantize", "reduce", "dequantize")
            for w in ("int8", "fp8")
        }
        failed = [r for r in rows if not r["lowered"]]
        assert not failed, failed
