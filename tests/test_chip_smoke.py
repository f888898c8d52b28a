"""chip_smoke.py off the chip: it must refuse to run, and its phases must
be right before a chip call is spent on them.

The script has no CPU option, so the rehearsal (guide: on-chip-measurement
§2, steps 1 and 2) lives here: the phase functions are imported and called
at a tiny width, on the CPU, with the kernels' interpret hook on — and the
four-chip phases on four of conftest's eight virtual devices.
"""

import importlib.util
import pathlib

import jax
import pytest

from paddle_tpu.core import compile_cache
from paddle_tpu.kernels import flash_attention as fa
from paddle_tpu.kernels import paged_attention as pa
from paddle_tpu.models import GPTConfig

ROOT = pathlib.Path(__file__).resolve().parent.parent


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture()
def interpret():
    fa._INTERPRET[0] = pa._INTERPRET[0] = True
    yield
    fa._INTERPRET[0] = pa._INTERPRET[0] = False


def _tiny(**kw):
    return GPTConfig(vocab_size=512, hidden_size=64, num_layers=2,
                     num_heads=4, max_seq_len=128, dtype="float32", **kw)


# -- no chip, no result -------------------------------------------------------
@pytest.mark.parametrize("argv", [[], ["--chips", "4"]])
def test_exits_nonzero_without_a_chip(smoke, capsys, argv):
    assert jax.devices()[0].platform == "cpu"
    with pytest.raises(SystemExit) as exc:
        smoke.main(argv)
    assert exc.value.code not in (0, None)
    assert "needs a TPU" in str(exc.value.code)
    assert '"ok"' not in capsys.readouterr().out


# -- compile cache: placeable from outside ------------------------------------
@pytest.fixture()
def cache_config():
    """enable() flips global jax config; put it back for the next test."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    saved = {n: getattr(jax.config, n) for n in names}
    yield
    for n, v in saved.items():
        jax.config.update(n, v)
    cc.reset_cache()


def test_cache_dir_is_fixed_in_checkout(monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert compile_cache.cache_dir() == str(ROOT / ".jax_cache")
    assert compile_cache.enable() == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == str(ROOT / ".jax_cache")
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()


def test_cache_dir_left_alone_when_env_names_one(monkeypatch, cache_config,
                                                 tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.cache_dir() is None
    assert compile_cache.enable() == str(tmp_path)
    # no directory set in code: whatever JAX read from the environment
    # at import is untouched
    assert jax.config.jax_compilation_cache_dir == before


# -- the rehearsal: phases at a tiny width under the interpret hook -----------
# ~40 s together, so out of tier-1 (which sits at its ceiling); run them
# before any chip call:  pytest tests/test_chip_smoke.py -m slow
@pytest.mark.slow
def test_phase_kernels_tiny(smoke, interpret):
    out = smoke.phase_kernels(flash_shape=(1, 128, 2, 16), rows=2, heads=2,
                              head_dim=16, block_size=4, max_blocks=4)
    assert out["ok"] and not out["compiled"]
    assert set(out["paged_decode"]["rel_err_vs_gather_twin"]) == {
        "bf16", "int8"}


@pytest.mark.slow
def test_phase_train_tiny(smoke, interpret):
    out = smoke.phase_train(
        _tiny(use_flash_attention=True, recompute="selective_lean"),
        batch=2, seq=128, fused_k=2)
    assert out["ok"] and len(out["step_s"]) == 3
    assert out["fused_losses"][-1] < out["losses"][0]


@pytest.mark.slow
def test_phase_serve_tiny(smoke, interpret):
    out = smoke.phase_serve(_tiny(use_flash_attention=False), rows=3,
                            prompt_range=(4, 40), quantum=4, new_tokens=6)
    assert out["ok"]
    assert [r["kv_kernel"] for r in out["runs"].values()] == [
        "pallas", "pallas"]
    assert out["runs"]["int8"]["kv_dtype"] == "int8"


@pytest.mark.slow
def test_phase_mesh_train_tiny(smoke, interpret):
    out = smoke.phase_mesh_train(
        _tiny(use_flash_attention=True, recompute="selective_lean"),
        batch=4, seq=128)
    assert out["ok"]
    shards = out["runs"]["dp2mp2"]["qkv_w_shards"]
    assert len(shards) == 4 and all(
        s == [2, 64, 96] for s in shards.values())


@pytest.mark.slow
def test_phase_mesh_serve_tiny(smoke, interpret):
    out = smoke.phase_mesh_serve(_tiny(use_flash_attention=False), rows=2,
                                 prompt_range=(8, 40), quantum=8,
                                 new_tokens=6)
    assert out["ok"] and out["identical_token_share"] >= 0.5
    assert all(s == [2, 17, 16, 1, 16]
               for s in out["mp4"]["kv_pool_shards"].values())
