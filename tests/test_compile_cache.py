"""Where the persistent compilation cache goes (repro.launch.compile_cache).
Path choice only: nothing here compiles or touches jax.config."""
from pathlib import Path

from repro.launch import compile_cache


def test_env_var_wins_and_is_reported_as_chosen_by_the_environment():
    path, from_env = compile_cache.compile_cache_dir(
        {"JAX_COMPILATION_CACHE_DIR": "/srv/jax-cache"}
    )
    assert (path, from_env) == ("/srv/jax-cache", True)


def test_unset_or_empty_env_var_falls_back_to_the_checkout():
    repo = Path(__file__).resolve().parents[1]
    for environ in ({}, {"JAX_COMPILATION_CACHE_DIR": ""}):
        path, from_env = compile_cache.compile_cache_dir(environ)
        assert not from_env
        assert Path(path) == repo / ".jax_cache"
