"""The two start-up helpers of the chip bring-up (PR 22): where the compile
cache lives, and which native binary may be loaded."""

import os
import shutil

import jax
import pytest

from filodb_tpu.utils import compilecache, nativebuild

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_placed_from_outside_is_left_to_jax(monkeypatch,
                                                          restore_cache_dir):
    jax.config.update("jax_compilation_cache_dir", None)
    monkeypatch.setenv(compilecache.CACHE_ENV, "/somewhere/else")
    assert compilecache.configure() == "/somewhere/else"
    assert jax.config.jax_compilation_cache_dir is None    # nothing set in code


def test_compile_cache_defaults_to_a_fixed_path_in_the_checkout(
        monkeypatch, restore_cache_dir):
    monkeypatch.delenv(compilecache.CACHE_ENV, raising=False)
    want = os.path.join(REPO, ".jax_cache")
    assert compilecache.configure() == want
    assert compilecache.configure() == want                 # idempotent
    assert jax.config.jax_compilation_cache_dir == want
    ignored = open(os.path.join(REPO, ".gitignore")).read().split()
    assert ".jax_cache/" in ignored and "*.so" in ignored


def test_native_build_is_keyed_on_content_and_ignores_foreign_binaries(
        tmp_path):
    src = tmp_path / "partset.cpp"
    shutil.copy(os.path.join(REPO, "filodb_tpu", "core", "native",
                             "partset.cpp"), src)
    # a binary left under the old fixed name (built elsewhere, or garbage):
    # never looked at, and swept once the keyed build lands
    foreign = tmp_path / "libdemo.so"
    foreign.write_text("not an ELF file")
    first = nativebuild.lib_path(str(src), "demo")
    lib = nativebuild.load(str(src), "demo")
    assert lib.ps_new is not None
    assert os.path.exists(first) and not foreign.exists()
    assert os.path.basename(first).startswith("libdemo-")
    # any change of the source changes the key: the old binary is not reused
    with open(src, "a") as f:
        f.write("\n// edited\n")
    second = nativebuild.lib_path(str(src), "demo")
    assert second != first
    nativebuild.load(str(src), "demo")
    assert os.path.exists(second) and not os.path.exists(first)


def test_native_build_failure_is_an_error_not_silence(tmp_path):
    bad = tmp_path / "broken.cpp"
    bad.write_text("this is not C++")
    with pytest.raises(nativebuild.NativeBuildError):
        nativebuild.load(str(bad), "broken")
