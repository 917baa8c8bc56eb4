"""The chip's entry points, as far as a CPU can check them: where the
compile cache goes, and that ``chip_smoke.py`` refuses to run without a
TPU (what it checks on one is its own job)."""

import os

from pipe_tpu.utils.platform import compile_cache_dir

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_compile_cache_dir_honours_env_else_fixed_checkout_path():
    """JAX_COMPILATION_CACHE_DIR set: the code sets nothing (JAX reads it).
    Unset or empty: the fixed in-checkout path — pure function of the
    environment, no backend init, nothing created."""
    assert compile_cache_dir({"JAX_COMPILATION_CACHE_DIR": "/some/dir"}) \
        is None
    for env in ({}, {"JAX_COMPILATION_CACHE_DIR": ""}):
        assert compile_cache_dir(env) == os.path.join(REPO, ".jax_cache")
    assert compile_cache_dir({}) == compile_cache_dir({})   # never moves


def test_chip_smoke_refuses_to_run_without_a_tpu(capsys):
    import chip_smoke

    assert chip_smoke.main() == 2
    out, err = capsys.readouterr()
    assert "no TPU found" in err and "'cpu'" in err
    assert out == ""                      # no result line without a chip
