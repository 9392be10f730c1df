"""Bring-up surfaces that run without a chip: the hardware model keyed by
`device_kind`, the fixed-path compilation cache, and `chip_smoke.py`'s
refusal to run off the chip plus its serve and check phases at a tiny
size on the CPU."""

import importlib.util
import pathlib
import types

import jax
import pytest

from repro import compile_cache
from repro.core import analysis, tune

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _device(platform, kind):
    return types.SimpleNamespace(platform=platform, device_kind=kind)


@pytest.mark.parametrize(
    "platform,kind,want",
    [
        ("tpu", "TPU v5 lite", analysis.TPU_V5E),
        ("cpu", "cpu", analysis.SKYLAKE_X),
        ("tpu", "TPU v9 imaginary", None),
    ],
)
def test_default_hw_is_keyed_by_device_kind(monkeypatch, platform, kind,
                                            want):
    monkeypatch.setattr(
        tune.jax, "devices", lambda: [_device(platform, kind)]
    )
    if want is None:  # a TPU without a model is an error, not a default
        with pytest.raises(ValueError, match="TPU v9 imaginary"):
            tune.default_hw()
    else:
        assert tune.default_hw() is want


def test_compile_cache_honours_the_env_var(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before  # JAX's own


def test_compile_cache_defaults_to_the_checkout(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        got = compile_cache.enable_compile_cache()
        assert got == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_wisdom_default_lives_in_the_checkout(monkeypatch):
    monkeypatch.delenv("REPRO_WISDOM", raising=False)
    assert tune._wisdom_path().parent == ROOT


@pytest.fixture
def smoke(monkeypatch, tmp_path):
    """chip_smoke.py as a module, its outputs under tmp_path."""
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    monkeypatch.setattr(mod, "OUT_DIR", tmp_path)
    return mod


def test_smoke_refuses_to_run_without_a_tpu(smoke, capsys):
    with pytest.raises(SystemExit) as exc:
        smoke.main([])
    assert exc.value.code not in (0, None)
    out, err = capsys.readouterr()
    assert "no TPU" in err
    assert '"ok"' not in out  # no result line


def _tiny_pool(smoke, monkeypatch):
    from repro.configs.convnets import vgg_mixed_channel
    from repro.convserve import Engine, ReplicaPool, init_weights

    monkeypatch.setattr(smoke, "SIDE", 32)
    monkeypatch.setattr(smoke, "MAX_BATCH", 4)
    spec = vgg_mixed_channel(c_in=3)
    weights = init_weights(spec, seed=smoke.SEED)
    engine = Engine()
    pool = ReplicaPool.build(engine, spec, weights, n=1, input_hw=(32, 32))
    return engine, spec, weights, pool


def test_smoke_serve_and_check_phases_at_a_tiny_size(smoke, monkeypatch):
    """The serve phase's accounting and the check phase's reference
    comparison, ragged requests included, on the CPU's matrix path."""
    engine, spec, weights, pool = _tiny_pool(smoke, monkeypatch)
    imgs = smoke.images((32, 32, 16, 24, 32), 3)
    results, times = smoke.phase_serve(pool, spec, imgs)
    assert sorted(results) == list(range(len(imgs)))
    assert times["warmup_s"] > 0
    errs = smoke.phase_check(engine, spec, weights, imgs, results)
    assert [e["side"] for e in errs] == [32, 32, 16, 24, 32]
    assert max(e["planned"] for e in errs) < smoke.REL_TOL


def test_smoke_plan_phase_refuses_a_host_backend(smoke, monkeypatch, capsys):
    """Off the chip the tile engine resolves to the XLA matrix path, and
    the plan phase must say so and stop."""
    from repro.configs.convnets import vgg_mixed_channel
    from repro.convserve import Engine, init_weights

    monkeypatch.setattr(smoke, "SIDE", 32)
    monkeypatch.setattr(smoke, "MAX_BATCH", 2)
    monkeypatch.delenv("REPRO_TILE_BACKEND", raising=False)
    spec = vgg_mixed_channel(c_in=3)
    with pytest.raises(SystemExit):
        smoke.phase_plan(
            Engine(hw=analysis.TPU_V5E), spec, init_weights(spec, seed=0)
        )
    assert "resolved to ['xla']" in capsys.readouterr().err
