"""Autotune disk-cache robustness: corrupt caches re-benchmark, never raise."""
import json

import pytest

from repro.engine import autotune
from repro.engine.autotune import autotune_bsi

GRID, TILE = (7, 7, 7), (2, 2, 2)


def _tune(cache):
    # the in-process memory cache would otherwise serve repeat calls before
    # the disk file is ever read — these tests exercise the DISK path
    autotune._MEM_CACHE.clear()
    return autotune_bsi(GRID, TILE, 2, reps=1, cache_path=str(cache),
                        candidates=(("ttli", "jnp"), ("separable", "jnp")))


@pytest.mark.parametrize("payload", [
    b"{ this is not json",          # garbage
    b'{"cpu|g7x7x7|t2x2x2|c2',      # truncated mid-write
    b"[1, 2, 3]",                   # valid JSON, wrong shape (not a dict)
    b"",                            # empty file
])
def test_corrupt_cache_triggers_clean_rebenchmark(tmp_path, payload):
    cache = tmp_path / "bsi_autotune.json"
    cache.write_bytes(payload)
    choice = _tune(cache)  # must not raise JSONDecodeError
    assert choice.mode in {"ttli", "separable"} and choice.us_per_call > 0
    # the re-benchmark rewrote the file as valid versioned JSON
    data = json.loads(cache.read_text())
    assert data["__schema__"] == autotune.SCHEMA_VERSION
    assert isinstance(data["entries"], dict) and len(data["entries"]) == 1


def test_stale_schema_cache_is_a_miss_not_an_error(tmp_path):
    """A disk cache written before the fused axis existed (SCHEMA_VERSION
    bump) must read as a clean miss — re-benchmark and rewrite — never a
    KeyError or a choice silently mis-dispatched with default fields."""
    cache = tmp_path / "bsi_autotune.json"
    # the v1 layout: a flat {key: choice} dict, no __schema__ wrapper
    stale_key = ("cpu|g7x7x7|t2x2x2|c2|"
                 "ttli/jnp,separable/jnp")
    cache.write_text(json.dumps({
        stale_key: {"mode": "ttli", "impl": "jnp", "us_per_call": 1.0}}))
    assert autotune._load_disk(str(cache)) == {}
    choice = _tune(cache)  # re-benchmarks instead of trusting the v1 entry
    assert choice.mode in {"ttli", "separable"} and choice.us_per_call > 0
    data = json.loads(cache.read_text())  # ... and upgraded the file
    assert data["__schema__"] == autotune.SCHEMA_VERSION
    # a future schema is equally a miss (no partial decode of unknown layouts)
    cache.write_text(json.dumps(
        {"__schema__": autotune.SCHEMA_VERSION + 1, "entries": {"k": {}}}))
    assert autotune._load_disk(str(cache)) == {}


def test_pre_matmul_v2_cache_is_a_miss_and_upgrades(tmp_path):
    """A v2 (pre-matmul) cache pinned winners measured without the MXU form
    in the race: the v3 bump must read it as a clean miss, re-benchmark with
    the enlarged candidate space and rewrite the file under v3."""
    assert autotune.SCHEMA_VERSION == 3  # this test documents the v2 -> v3 bump
    cache = tmp_path / "bsi_autotune.json"
    stale_key = "cpu|g7x7x7|t2x2x2|c2|ttli/jnp,separable/jnp"
    cache.write_text(json.dumps({
        "__schema__": 2,
        "entries": {stale_key: {"mode": "ttli", "impl": "jnp",
                                "us_per_call": 1.0, "grad_impl": "xla",
                                "fused": "off"}}}))
    assert autotune._load_disk(str(cache)) == {}  # well-formed v2 != a hit
    choice = _tune(cache)
    assert choice.mode in {"ttli", "separable"} and choice.us_per_call > 0
    data = json.loads(cache.read_text())
    assert data["__schema__"] == 3  # the rewrite upgraded the schema
    # the v2 entry did not survive into the rewritten file
    assert all(v.get("us_per_call") != 1.0 for v in data["entries"].values())


def test_malformed_entry_is_a_miss_not_an_error(tmp_path):
    cache = tmp_path / "bsi_autotune.json"
    first = _tune(cache)
    data = json.loads(cache.read_text())
    (key,) = data["entries"]
    # hand-edit the entry into nonsense: missing fields / wrong types
    for bad in ({}, {"mode": "ttli"}, {"mode": "ttli", "impl": "jnp",
                                       "us_per_call": "fast"},
                {"mode": "ttli", "impl": "jnp", "us_per_call": 1.0,
                 "fused": "sideways"}, "zap"):
        cache.write_text(json.dumps({"__schema__": autotune.SCHEMA_VERSION,
                                     "entries": {key: bad}}))
        again = _tune(cache)  # re-measures; winner may differ (timing noise)
        assert again.mode in {"ttli", "separable"} and again.us_per_call > 0
    assert first.us_per_call > 0


def test_valid_cache_entry_still_round_trips(tmp_path):
    cache = tmp_path / "bsi_autotune.json"
    first = _tune(cache)
    # rewrite the file as-is; a fresh read must serve the stored choice
    data = json.loads(cache.read_text())
    cache.write_text(json.dumps(data))
    assert _tune(cache) == first


def test_per_similarity_cache_keys_are_distinct(tmp_path):
    """measure_grad timing is per-similarity: nmi's backward is a different
    workload mix than ssd's, so each gets its own cache entry."""
    cache = tmp_path / "bsi_autotune.json"
    for sim in ("ssd", "nmi"):
        choice = autotune_bsi(GRID, TILE, 3, reps=1, cache_path=str(cache),
                              candidates=(("ttli", "jnp"),
                                          ("separable", "jnp")),
                              measure_grad=True, similarity=sim)
        assert choice.us_per_call > 0
    entries = json.loads((cache).read_text())["entries"]
    assert len(entries) == 2
    assert any("|sim=ssd|" in k for k in entries)
    assert any("|sim=nmi|" in k for k in entries)


def test_fused_race_entry_round_trips(tmp_path, monkeypatch):
    """autotune_fused caches its decision under the current schema and serves
    it back without re-measuring (us_per_call would differ on a re-race)."""
    # force the actual measurement on CPU hosts (same override that admits
    # interpret-mode Pallas into default_candidates)
    monkeypatch.setenv("REPRO_AUTOTUNE_PALLAS", "1")
    cache = tmp_path / "bsi_autotune.json"
    base = autotune.BsiChoice("separable", "jnp", 0.0, "jnp")
    autotune._MEM_CACHE.clear()
    first = autotune.autotune_fused(GRID, TILE, (8, 8, 8), base=base,
                                    similarity="ssd", reps=1,
                                    cache_path=str(cache))
    assert first.fused in ("on", "off") and first.us_per_call > 0
    autotune._MEM_CACHE.clear()
    again = autotune.autotune_fused(GRID, TILE, (8, 8, 8), base=base,
                                    similarity="ssd", reps=1,
                                    cache_path=str(cache))
    assert again == first
    entries = json.loads(cache.read_text())["entries"]
    assert any("|fused|" in k for k in entries)


def _failing_interpolate(monkeypatch, bad_mode, err):
    """Make one candidate form raise ``err`` while it is traced."""
    real = autotune.interpolate

    def fake(p, tile, *, mode, **kw):
        if mode == bad_mode:
            raise err
        return real(p, tile, mode=mode, **kw)

    monkeypatch.setattr(autotune, "interpolate", fake)


def test_non_memory_error_propagates_from_autotune_bsi(tmp_path,
                                                       monkeypatch):
    """Only out-of-memory skips a candidate: any other failure is a bug the
    race must not hide behind a slower winner."""
    _failing_interpolate(monkeypatch, "ttli", TypeError("kernel bug"))
    autotune._MEM_CACHE.clear()
    with pytest.raises(TypeError, match="kernel bug"):
        autotune_bsi(GRID, TILE, 2, reps=1, use_cache=False,
                     candidates=(("separable", "jnp"), ("ttli", "jnp")))


def test_out_of_memory_candidate_is_skipped_with_reason(tmp_path,
                                                        monkeypatch):
    import jax

    oom = jax.errors.JaxRuntimeError(
        "RESOURCE_EXHAUSTED: Ran out of memory in memory space hbm")
    _failing_interpolate(monkeypatch, "ttli", oom)
    cache = tmp_path / "c.json"
    autotune._MEM_CACHE.clear()
    choice = autotune_bsi(GRID, TILE, 2, reps=1, cache_path=str(cache),
                          candidates=(("separable", "jnp"), ("ttli", "jnp")))
    assert (choice.mode, choice.impl) == ("separable", "jnp")
    assert choice.skipped == (
        ("ttli/jnp", "RESOURCE_EXHAUSTED: Ran out of memory in memory "
                     "space hbm"),)
    # the skipped list survives the disk cache
    autotune._MEM_CACHE.clear()
    again = autotune_bsi(GRID, TILE, 2, reps=1, cache_path=str(cache),
                         candidates=(("separable", "jnp"), ("ttli", "jnp")))
    assert again == choice


def test_candidate_without_memory_headroom_is_skipped(monkeypatch):
    """A compiled step that would take more than STEP_MEMORY_SHARE of the
    device is skipped (it may compile, then fail to load beside the rest of
    the registration); with every candidate skipped the tuner says why."""
    monkeypatch.setattr(autotune, "_device_bytes_limit", lambda dev: 1024)
    autotune._MEM_CACHE.clear()
    with pytest.raises(RuntimeError, match="needs .* GiB of the device"):
        autotune_bsi(GRID, TILE, 2, reps=1, use_cache=False,
                     candidates=(("separable", "jnp"), ("ttli", "jnp")))


def test_non_memory_error_propagates_from_autotune_fused(monkeypatch):
    from repro.core import ffd

    monkeypatch.setenv("REPRO_AUTOTUNE_PALLAS", "1")

    def broken(*a, **kw):
        raise TypeError("fused kernel bug")

    monkeypatch.setattr(ffd, "fused_warp_loss", broken)
    base = autotune.BsiChoice("separable", "jnp", 0.0, "jnp")
    autotune._MEM_CACHE.clear()
    with pytest.raises(TypeError, match="fused kernel bug"):
        autotune.autotune_fused(GRID, TILE, (8, 8, 8), base=base,
                                similarity="ssd", reps=1, use_cache=False)


def test_resolve_options_reports_skipped_candidates(tmp_path, monkeypatch):
    import jax

    from repro.core import RegistrationOptions
    from repro.engine.autotune import resolve_options

    oom = jax.errors.JaxRuntimeError("RESOURCE_EXHAUSTED: out of memory")
    _failing_interpolate(monkeypatch, "ttli", oom)
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "c.json"))
    autotune._MEM_CACHE.clear()
    opts = resolve_options(RegistrationOptions(
        tile=TILE, impl="jnp", grad_impl="jnp", fused="off"), (9, 8, 7))
    assert opts.mode != "ttli"
    assert ("ttli/jnp/jnp", "RESOURCE_EXHAUSTED: out of memory") \
        in opts.skipped
