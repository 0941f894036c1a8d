"""chip_smoke.py off the chip: the dry run walks all three legs, no chip is
no pass, and the pieces the smoke leans on (compile-cache placement, the
native build stamp, the attention-path record) say what they should."""

import ctypes
import os
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")

# leg C refuses any tier but the native one, which is built on first use
needs_toolchain = pytest.mark.skipif(
    shutil.which("g++") is None or shutil.which("make") is None,
    reason="no native toolchain",
)


def _run_smoke(*args: str, **env: str) -> subprocess.CompletedProcess:
    return subprocess.run(
        [sys.executable, SMOKE, *args],
        cwd=REPO,
        env={**os.environ, **env},
        capture_output=True,
        text=True,
        timeout=300,
    )


@needs_toolchain
def test_dry_run_walks_all_legs_and_never_prints_the_pass_line() -> None:
    done = _run_smoke(
        "--dry-run", XLA_FLAGS="--xla_force_host_platform_device_count=4"
    )
    assert done.returncode == 0, done.stderr[-3000:]
    lines = done.stdout.strip().splitlines()
    assert lines[-1] == "dry_run=true platform=cpu"
    assert '"ok"' not in done.stdout
    for leg in "ABC":
        assert any(l.startswith(f"leg {leg}:") for l in lines), leg
    # 4 virtual devices: two replica groups of two, on disjoint devices
    assert any("2 replicas x 2 chips" in l for l in lines)


def test_without_a_chip_and_without_the_flag_it_fails() -> None:
    done = _run_smoke(JAX_PLATFORMS="cpu")
    assert done.returncode != 0
    assert done.stdout.strip() == ""  # no result
    assert "no TPU" in done.stderr


@needs_toolchain
def test_two_replicas_sharing_one_device(monkeypatch) -> None:
    """The one-chip layout of leg C: both replica groups on the same
    device."""
    import chip_smoke

    monkeypatch.setenv("TORCHFT_FLASH", "1")  # as the dry run sets it
    chip_smoke.leg_c(True, devices=jax.devices()[:1])


class TestCompileCachePlacement:
    def test_env_set_means_code_sets_nothing(self, monkeypatch, tmp_path) -> None:
        from torchft_tpu.utils import compile_cache

        def refuse(*a, **kw):
            raise AssertionError(f"jax.config.update{a} with the env set")

        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        monkeypatch.setattr(jax.config, "update", refuse)
        assert compile_cache.configure_compile_cache() == str(tmp_path)

    def test_unset_means_the_checkout(self, monkeypatch) -> None:
        from torchft_tpu.utils import compile_cache

        calls = []
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        monkeypatch.setattr(jax.config, "update", lambda *a: calls.append(a))
        want = os.path.join(REPO, ".jax_cache")
        assert compile_cache.configure_compile_cache() == want
        assert calls == [("jax_compilation_cache_dir", want)]


@needs_toolchain
def test_stale_native_stamp_forces_a_rebuild(tmp_path) -> None:
    """A libtpuft.so that was not built here from these sources (no stamp,
    or another tree's) is rebuilt, never loaded; a stamped one is kept with
    nothing written, so a read-only install loads it."""
    from torchft_tpu import native

    src = os.path.join(REPO, "native")
    for name in os.listdir(src):
        if name.endswith((".cc", ".h")) or name == "Makefile":
            shutil.copy(os.path.join(src, name), tmp_path / name)
    lib = tmp_path / "libtpuft.so"
    lib.write_bytes(b"built somewhere else")
    (tmp_path / "libtpuft.so.stamp").write_text("another tree")

    native._build_lib(str(tmp_path), str(lib))
    ctypes.CDLL(str(lib))  # a real shared object now
    stamp = (tmp_path / "libtpuft.so.stamp").read_text()
    assert stamp == native._build_stamp(str(tmp_path))

    built = os.stat(lib).st_mtime_ns
    os.remove(str(lib) + ".lock")
    native._build_lib(str(tmp_path), str(lib))
    assert os.stat(lib).st_mtime_ns == built  # fresh: not rebuilt,
    assert not os.path.exists(str(lib) + ".lock")  # and nothing written

    with open(tmp_path / "comm.h", "a") as f:
        f.write("\n// edited\n")
    assert native._build_stamp(str(tmp_path)) != stamp


def test_attention_path_is_recorded_with_its_reason(monkeypatch) -> None:
    from torchft_tpu.models.llama import Llama, llama_debug

    def trace(seq: int) -> str:
        model = Llama(llama_debug())
        params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        tokens = jax.ShapeDtypeStruct((2, seq), jnp.int32)
        jax.eval_shape(model.loss, params, (tokens, tokens))
        return model.attention_path

    monkeypatch.delenv("TORCHFT_FLASH", raising=False)
    assert trace(128) == "naive: backend is cpu, not tpu"
    monkeypatch.setenv("TORCHFT_FLASH", "1")
    assert trace(128) == "flash"
    assert trace(100).startswith("naive: seq=100 ")
    monkeypatch.setenv("TORCHFT_FLASH", "0")
    assert trace(128) == "naive: TORCHFT_FLASH=0"
