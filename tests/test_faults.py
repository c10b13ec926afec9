"""Fault-injection harness: arming (API + env), Nth-hit firing, fault kinds,
counters, and /metrics surfacing."""

import pytest

from albedo_tpu.utils import events, faults
from albedo_tpu.utils.faults import FaultInjected, FaultRegistry, FaultSpec


def test_unarmed_site_is_a_noop():
    faults.hit("nothing.armed")
    assert faults.FAULTS.hits("nothing.armed") == 1
    assert faults.FAULTS.fired("nothing.armed") == 0


def test_fires_at_nth_hit_once():
    s = faults.site("t.nth")
    s.arm(kind="error", at=3)
    s.hit()
    s.hit()
    with pytest.raises(FaultInjected):
        s.hit()
    s.hit()  # times=1: only the 3rd hit fires
    assert s.fired() == 1
    assert s.hits() == 4


def test_fires_for_m_consecutive_hits():
    s = faults.site("t.window")
    s.arm(kind="error", at=2, times=2)
    s.hit()
    for _ in range(2):
        with pytest.raises(FaultInjected):
            s.hit()
    s.hit()  # window over
    assert s.fired() == 2


def test_forever_window():
    s = faults.site("t.forever")
    s.arm(kind="error", at=1, times=0)
    for _ in range(3):
        with pytest.raises(FaultInjected):
            s.hit()
    assert s.fired() == 3


def test_ioerror_kind():
    s = faults.site("t.io")
    s.arm(kind="ioerror")
    with pytest.raises(OSError):
        s.hit()


def test_corrupt_kind_flips_a_byte(tmp_path):
    p = tmp_path / "artifact.bin"
    p.write_bytes(b"\x00" * 100)
    s = faults.site("t.corrupt")
    s.arm(kind="corrupt")
    s.hit(path=p)
    data = p.read_bytes()
    assert len(data) == 100 and data != b"\x00" * 100


def test_corrupt_without_path_is_noop():
    s = faults.site("t.corrupt2")
    s.arm(kind="corrupt")
    s.hit()  # nothing to flip: no error
    assert s.fired() == 1


def test_corrupt_directory_targets_first_file(tmp_path):
    d = tmp_path / "ckpt"
    d.mkdir()
    (d / "a.bin").write_bytes(b"\x01\x02\x03\x04")
    before = (d / "a.bin").read_bytes()
    s = faults.site("t.corruptdir")
    s.arm(kind="corrupt")
    s.hit(path=d)
    assert (d / "a.bin").read_bytes() != before


def test_delay_kind_sleeps(monkeypatch):
    naps = []
    import albedo_tpu.utils.faults as faults_mod

    monkeypatch.setattr(faults_mod.time, "sleep", naps.append)
    s = faults.site("t.delay")
    s.arm(kind="delay", param=0.25)
    s.hit()
    assert naps == [0.25]


def test_env_spec_parsing():
    reg = FaultRegistry(env="a.load:corrupt@2,b.save:kill,c.x:error@3*0")
    assert reg.armed("a.load") == [FaultSpec("a.load", "corrupt", at=2)]
    assert reg.armed("b.save")[0].kind == "kill"
    c = reg.armed("c.x")[0]
    assert (c.at, c.times) == (3, 0)


def test_env_spec_bad_kind_raises():
    with pytest.raises(ValueError):
        FaultRegistry(env="a.b:frobnicate")


def test_env_spec_parse_error_names_the_env_var():
    """A typo'd ALBEDO_FAULTS crashes at import in whatever process it leaks
    into — the error must say where the bad value came from."""
    with pytest.raises(ValueError, match=r"ALBEDO_FAULTS.*kill@two"):
        FaultRegistry(env="checkpoint.save:kill@two")


def test_fired_counter_reaches_global_metrics():
    before = events.faults_fired.value(site="t.metric")
    s = faults.site("t.metric")
    s.arm(kind="error")
    with pytest.raises(FaultInjected):
        s.hit()
    assert events.faults_fired.value(site="t.metric") == before + 1


def test_jax_cache_writes_become_atomic(tmp_path):
    """The torn-write hardening: after harden_jax_cache_writes, a cache put
    lands via tmp+rename (no .albedo-tmp residue on success) and the entry
    round-trips."""
    pytest.importorskip("jax")
    from albedo_tpu.utils.compilation_cache import harden_jax_cache_writes

    harden_jax_cache_writes()
    from jax._src import lru_cache as _lc

    cache = _lc.LRUCache(str(tmp_path / "cache"), max_size=-1)
    cache.put("k1", b"\x01" * 64)
    assert cache.get("k1") == b"\x01" * 64
    names = sorted(p.name for p in (tmp_path / "cache").iterdir())
    # Eviction off (max_size=-1): jax 0.9.0 writes no atime file, and
    # neither does the patch.
    assert names == ["k1-cache"]

    evicting = _lc.LRUCache(str(tmp_path / "evicting"), max_size=1 << 20)
    evicting.put("k2", b"\x02" * 64)
    assert evicting.get("k2") == b"\x02" * 64
    names = sorted(p.name for p in (tmp_path / "evicting").iterdir())
    assert "k2-cache" in names and "k2-atime" in names
    assert not any(".albedo-tmp-" in n for n in names)


def test_stale_cache_tmp_files_swept(tmp_path, monkeypatch):
    """Tmp files a killed writer left in the cache dir are removed when the
    cache is (re-)enabled."""
    pytest.importorskip("jax")
    import albedo_tpu.utils.compilation_cache as cc

    import os as _os
    import time as _time

    cache_dir = tmp_path / "jax-cache"  # where conftest placed the cache
    cache_dir.mkdir()
    stale = cache_dir / "k9.albedo-tmp-12345"
    stale.write_bytes(b"torn")
    _os.utime(stale, (0, _time.time() - 7200))  # 2h old: genuinely stale
    fresh = cache_dir / "k10.albedo-tmp-99999"
    fresh.write_bytes(b"in-flight")  # young: may belong to a live writer
    monkeypatch.setattr(cc, "_ENABLED", False)
    assert cc.enable_persistent_compilation_cache() is True
    assert not stale.exists()  # old residue swept
    assert fresh.exists()  # live writer's tmp untouched (age gate)


def test_global_counters_render_on_metrics_page():
    pytest.importorskip("jax")
    from albedo_tpu.serving.metrics import MetricsRegistry

    text = MetricsRegistry().render()
    # The offline fault-tolerance catalog rides every exposition.
    assert "albedo_artifact_corruptions_total" in text
    assert "albedo_checkpoint_fallbacks_total" in text
    assert "albedo_retry_attempts_total" in text
    assert "albedo_faults_fired_total" in text
