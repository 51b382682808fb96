"""Persistent autotune cache (kernels/tuning.py).

The ``choose_*`` block pickers are wrapped by ``persistent_choice``: an
in-memory lru_cache backed by an on-disk JSON file so tuned choices
survive process restarts.  Contracts:

  - the env var REPRO_TUNE_CACHE overrides the path; ''/0/off/none
    disables persistence entirely;
  - entries round-trip through JSON (tuples come back as tuples);
  - a disk entry WINS over recomputation (that is the point: a measured
    choice recorded once is honored later), keyed by function, args and
    ambient shard topology;
  - IO failure is non-fatal — the picker still returns a valid choice.
"""
import json
import os

import pytest

from repro.kernels import tuning


@pytest.fixture
def tmp_cache(tmp_path, monkeypatch):
    """Route the cache to a temp file and leave global state clean."""
    path = str(tmp_path / "tuning.json")
    monkeypatch.setenv("REPRO_TUNE_CACHE", path)
    tuning.clear_tune_cache()
    yield path
    tuning.clear_tune_cache()


def test_cache_path_env_override(monkeypatch):
    monkeypatch.setenv("REPRO_TUNE_CACHE", "/some/where/t.json")
    assert tuning.tune_cache_path() == "/some/where/t.json"
    for off in ("", "0", "off", "none", "OFF"):
        monkeypatch.setenv("REPRO_TUNE_CACHE", off)
        assert tuning.tune_cache_path() is None


def test_choice_written_to_disk(tmp_cache):
    val = tuning.choose_gs_block(33, 8192, "float32")
    assert os.path.exists(tmp_cache)
    with open(tmp_cache) as f:
        disk = json.load(f)
    key = [k for k in disk if k.startswith("choose_gs_block|")]
    assert key, disk
    assert disk[key[0]] == val


def test_disk_entry_wins_over_recomputation(tmp_cache):
    """Seed the file with a poisoned value; the lookup must honor it."""
    computed = tuning.choose_matvec_blocks(256, 1024)
    with open(tmp_cache) as f:
        disk = json.load(f)
    (key,) = [k for k in disk if k.startswith("choose_matvec_blocks|")]
    poisoned = [8, 128]
    disk[key] = poisoned
    with open(tmp_cache, "w") as f:
        json.dump(disk, f)
    tuning.clear_tune_cache()            # drop memory; keep the file
    got = tuning.choose_matvec_blocks(256, 1024)
    assert got == tuple(poisoned) != computed


def test_tuple_round_trip_through_json(tmp_cache):
    first = tuning.choose_matvec_blocks(512, 2048)
    assert isinstance(first, tuple)
    tuning.clear_tune_cache()
    again = tuning.choose_matvec_blocks(512, 2048)
    assert again == first and isinstance(again, tuple)


def test_key_includes_topology(tmp_cache):
    tuning.choose_gs_block(17, 4096, "float32")
    with open(tmp_cache) as f:
        disk = json.load(f)
    assert all(f"|p{tuning.shard_size()}" in k for k in disk), disk


def test_disabled_cache_writes_nothing(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_TUNE_CACHE", "off")
    tuning.clear_tune_cache()
    try:
        val = tuning.choose_gs_block(33, 4096, "float32")
        assert val > 0
        assert not list(tmp_path.iterdir())
    finally:
        tuning.clear_tune_cache()


def test_unwritable_path_is_non_fatal(monkeypatch):
    monkeypatch.setenv("REPRO_TUNE_CACHE",
                       "/proc/definitely/not/writable/t.json")
    tuning.clear_tune_cache()
    try:
        assert tuning.choose_gs_block(33, 4096, "float32") > 0
    finally:
        tuning.clear_tune_cache()


def test_clear_disk_removes_file(tmp_cache):
    tuning.choose_gs_block(33, 8192, "float32")
    assert os.path.exists(tmp_cache)
    tuning.clear_tune_cache(disk=True)
    assert not os.path.exists(tmp_cache)


def test_record_tuned_overwrites_model_choice(tmp_cache):
    """Autotune-by-measurement: a recorded timing winner must outrank the
    VMEM-model choice for the same key — now and after a cache reload."""
    modeled = tuning.choose_spmv_block(4096, 9, "float32", k=1)
    measured = 128 if modeled != 128 else 256
    key = tuning.record_tuned(tuning.choose_spmv_block, measured,
                              4096, 9, "float32", k=1)
    assert key.startswith("choose_spmv_block|")
    assert tuning.choose_spmv_block(4096, 9, "float32", k=1) == measured
    # Survives a full in-memory drop (the restart story).
    tuning.clear_tune_cache()
    assert tuning.choose_spmv_block(4096, 9, "float32", k=1) == measured
    # Other keys are untouched by the overwrite.
    assert tuning.choose_spmv_block(4096, 9, "float32", k=4) != measured or \
        tuning.choose_spmv_block.__wrapped__(4096, 9, "float32", k=4) == measured


def test_record_tuned_tuple_values(tmp_cache):
    tuning.record_tuned(tuning.choose_matvec_blocks, (64, 256), 512, 2048)
    got = tuning.choose_matvec_blocks(512, 2048)
    assert got == (64, 256) and isinstance(got, tuple)


def test_record_tuned_rejects_plain_functions():
    with pytest.raises(TypeError):
        tuning.record_tuned(lambda n: n, 128, 64)


def test_gs_payload_fits_gate():
    """The explicit dispatch gate for the single-reduce payload kernel."""
    assert tuning.gs_payload_fits(33, 8192, "float32")
    assert not tuning.gs_payload_fits(33, 8192, "float32", budget=16)
    assert not tuning.gs_payload_fits(33, 0, "float32")


def test_compile_cache_dir_is_fixed(tmp_path, monkeypatch):
    """JAX's persistent compile cache: the directory the environment names
    is left to JAX itself; without it the cache goes to ``.jax_cache/`` at
    the repository root — one fixed path, so a later run finds it."""
    import jax

    from repro.runtime import compile_cache

    prev = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
    assert compile_cache.configure() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == prev
    monkeypatch.delenv(compile_cache.ENV)
    try:
        assert compile_cache.configure() == str(compile_cache.REPO_CACHE)
        assert jax.config.jax_compilation_cache_dir == str(
            compile_cache.REPO_CACHE)
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
    root = compile_cache.REPO_CACHE.parent
    assert compile_cache.REPO_CACHE.name == ".jax_cache"
    assert (root / "chip_smoke.py").is_file()
    assert ".jax_cache/" in (root / ".gitignore").read_text().split()
