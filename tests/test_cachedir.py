"""Where compiled programs persist (engine/cachedir.py): placed from outside
by ``JAX_COMPILATION_CACHE_DIR``, else a fixed path in the checkout — never
derived from a server's state directory, which soaks and smokes root in a
fresh temp dir every run (a cache that moves never hits)."""

import dataclasses
import os

import jax
import pytest

from vilbert_multitask_tpu.engine import cachedir


@pytest.fixture()
def config_updates(monkeypatch):
    """Record ``jax.config.update`` calls without applying the directory
    ones: the session's real compilation cache (tests/conftest.py) must not
    be re-pointed by a test about where it would point."""
    calls = []
    real_update = jax.config.update

    def update(name, value):
        calls.append((name, value))
        if name != "jax_compilation_cache_dir":
            real_update(name, value)

    monkeypatch.setattr(jax.config, "update", update)
    monkeypatch.setattr(cachedir.compilation_cache, "reset_cache",
                        lambda: None)
    return calls


def test_env_places_the_cache_and_code_sets_no_directory(
        monkeypatch, config_updates, tmp_path):
    monkeypatch.setenv(cachedir.CACHE_DIR_ENV, str(tmp_path / "outside"))
    assert cachedir.enable_compilation_cache() == str(tmp_path / "outside")
    assert "jax_compilation_cache_dir" not in [n for n, _ in config_updates]
    # ...while every compile still persists there (floor 0 s).
    assert ("jax_persistent_cache_min_compile_time_secs", 0.0) \
        in config_updates


def test_unset_env_is_one_fixed_checkout_path_for_every_serveapp(
        monkeypatch, config_updates, tiny_framework_cfg, engine, tmp_path):
    from vilbert_multitask_tpu.serve.app import ServeApp

    monkeypatch.delenv(cachedir.CACHE_DIR_ENV)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    seen = []
    for name in ("state_a", "state_b"):
        root = tmp_path / name
        root.mkdir()
        cfg = dataclasses.replace(
            tiny_framework_cfg,
            serving=dataclasses.replace(
                tiny_framework_cfg.serving,
                queue_db_path=str(root / "q.sqlite3"),
                results_db_path=str(root / "r.sqlite3"),
                media_root=str(root / "media")))
        app = ServeApp(cfg, engine=engine)
        seen.append((app.boot_info["compile_cache_dir"],
                     app.cfg.engine.aot_cache_dir))
    # Two servers, two state dirs, ONE cache location — inside the
    # checkout, not beside either state dir.
    assert seen[0] == seen[1] == (os.path.join(repo, ".jax_cache"),
                                  os.path.join(repo, ".aot_cache"))
    assert ("jax_compilation_cache_dir",
            os.path.join(repo, ".jax_cache")) in config_updates
