"""Where the launchers put JAX's persistent compilation cache."""
import jax

from repro import compile_cache


def _placed(monkeypatch, env):
    before = jax.config.jax_compilation_cache_dir
    if env is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env)
    try:
        compile_cache.place_compile_cache()
        return jax.config.jax_compilation_cache_dir
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_cache_defaults_to_fixed_dir_in_checkout(monkeypatch):
    placed = _placed(monkeypatch, None)
    assert placed == str(compile_cache.CACHE_DIR)
    assert compile_cache.CACHE_DIR.parent.joinpath("src", "repro").is_dir()
    assert placed == _placed(monkeypatch, None)  # same path on every call


def test_cache_follows_env_when_set(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    assert _placed(monkeypatch, str(tmp_path)) == before  # JAX reads the env itself


def test_tpu_logs_default_off(monkeypatch):
    monkeypatch.delenv("TPU_LOG_DIR", raising=False)
    compile_cache.place_tpu_logs()
    assert compile_cache.os.environ["TPU_LOG_DIR"] == compile_cache.TPU_LOG_DEFAULT


def test_tpu_logs_follow_env_when_set(monkeypatch, tmp_path):
    monkeypatch.setenv("TPU_LOG_DIR", str(tmp_path))
    compile_cache.place_tpu_logs()
    assert compile_cache.os.environ["TPU_LOG_DIR"] == str(tmp_path)
