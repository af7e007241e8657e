"""TTL cache + path resolver + name/config utilities."""

import pytest

from hyperspace_tpu.config import HyperspaceConf, INDEX_NUM_BUCKETS
from hyperspace_tpu.exceptions import HyperspaceError
from hyperspace_tpu.index.index_config import IndexConfig
from hyperspace_tpu.metadata.cache import CreationTimeBasedCache
from hyperspace_tpu.metadata.path_resolver import PathResolver
from hyperspace_tpu.utils.name_utils import normalize_index_name


def test_cache_ttl(monkeypatch):
    import time as time_mod

    # The TTL clock is monotonic (clock-step hazard: an NTP step must
    # not expire fresh entries or immortalize stale ones).
    t = [1000.0]
    monkeypatch.setattr(time_mod, "monotonic", lambda: t[0])
    c = CreationTimeBasedCache(expiry_seconds=10)
    assert c.get() is None
    c.set([1, 2, 3])
    assert c.get() == [1, 2, 3]
    t[0] += 11
    assert c.get() is None  # expired
    c.set([4])
    assert c.get() == [4]
    c.clear()
    assert c.get() is None


def test_path_resolver_case_insensitive(tmp_path):
    conf = HyperspaceConf(system_path=str(tmp_path))
    r = PathResolver(conf)
    (tmp_path / "MyIndex").mkdir()
    assert r.get_index_path("myindex") == tmp_path / "MyIndex"
    assert r.get_index_path("MYINDEX") == tmp_path / "MyIndex"
    # Unknown names resolve to normalized child path.
    assert r.get_index_path("new idx") == tmp_path / "new_idx"
    assert r.list_index_paths() == [tmp_path / "MyIndex"]


def test_normalize_index_name():
    assert normalize_index_name("  my  index \t name ") == "my_index_name"


def test_conf_overrides():
    conf = HyperspaceConf(system_path="/x")
    conf.set(INDEX_NUM_BUCKETS, 16)
    assert conf.num_buckets == 16
    assert conf.get(INDEX_NUM_BUCKETS) == 16


def test_index_config_validation():
    with pytest.raises(HyperspaceError):
        IndexConfig("", ["a"])
    with pytest.raises(HyperspaceError):
        IndexConfig("i", [])
    with pytest.raises(HyperspaceError):
        IndexConfig("i", ["a", "A"])
    with pytest.raises(HyperspaceError):
        IndexConfig("i", ["a"], ["A"])
    cfg = IndexConfig.builder().index_name("i").indexed_columns("a").included_columns("b").create()
    assert cfg == IndexConfig("I", ["A"], ["B"])  # case-insensitive equality
    assert cfg.all_columns == ["a", "b"]


def test_index_config_builder_double_set():
    b = IndexConfig.builder().index_name("i")
    with pytest.raises(HyperspaceError):
        b.index_name("j")


@pytest.mark.parametrize("env,preset,root,expect", [
    ("/elsewhere", None, "repo", None),  # the variable wins; nothing set in code
    (None, "/caller", "repo", None),  # a caller's jax.config setting stays
    (None, None, "site-packages", None),  # installed package: no cache
    (None, None, "repo", ".jax_cache"),  # a checkout: one fixed path inside it
])
def test_compile_cache_setter_only_fills_an_unset_cache(tmp_path, monkeypatch, env, preset, root, expect):
    import types

    from hyperspace_tpu.parallel import mesh as mesh_mod

    updates = {}
    fake_jax = types.SimpleNamespace(config=types.SimpleNamespace(
        jax_compilation_cache_dir=preset, update=lambda k, v: updates.__setitem__(k, v),
    ))
    monkeypatch.setattr(mesh_mod, "jax", fake_jax)
    monkeypatch.setattr(mesh_mod, "_cache_enabled", False)
    monkeypatch.setattr(mesh_mod, "_REPO_ROOT", tmp_path / root)
    if env is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env)
    mesh_mod.enable_compile_cache()
    got = updates.get("jax_compilation_cache_dir")
    assert got == (None if expect is None else str(tmp_path / root / expect))
