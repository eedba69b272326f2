"""Mirror checkpoint tests: snapshot save/restore + engine warm restart."""

import numpy as np
import pytest

from keto_tpu.config import Config
from keto_tpu.engine.checkpoint import (
    load_snapshot,
    save_snapshot,
    stable_fingerprint,
)
from keto_tpu.engine.snapshot import build_snapshot
from keto_tpu.engine.tpu_engine import TPUCheckEngine
from keto_tpu.ketoapi import RelationTuple
from keto_tpu.namespace.ast import ComputedSubjectSet, Relation, SubjectSetRewrite
from keto_tpu.namespace.definitions import Namespace
from keto_tpu.storage.memory import MemoryManager


def ts(*strs):
    return [RelationTuple.from_string(s) for s in strs]


NAMESPACES = [
    Namespace(
        name="files",
        relations=[
            Relation(name="owner"),
            Relation(
                name="view",
                subject_set_rewrite=SubjectSetRewrite(
                    children=[ComputedSubjectSet(relation="owner")]
                ),
            ),
        ],
    )
]

TUPLES = ts(
    "files:a#owner@alice",
    "files:a#view@(files:b#owner)",
    "files:b#owner@bob",
    "files:weird name#owner@user with spaces",
)


def forge_retired_layout(path):
    """Make a sound v4 checkpoint read as an older CPU process wrote one:
    the meta vector's last entry the retired one-slot-a-bucket code. (The
    tables are left as built: nothing may get as far as probing them.)"""
    with np.load(path, allow_pickle=False) as z:
        arrays = {k: z[k] for k in z.files}
    assert arrays["meta"][0] == 4 and arrays["meta"][-1] == 0
    arrays["meta"][-1] = 1
    with open(path, "wb") as f:
        np.savez(f, **arrays)


class TestStableFingerprint:
    def test_deterministic(self):
        a = stable_fingerprint([{"x": 1}, "y"])
        assert a == stable_fingerprint([{"x": 1}, "y"])
        assert a != stable_fingerprint([{"x": 2}, "y"])


class TestSnapshotRoundtrip:
    def test_roundtrip_equality(self, tmp_path):
        snap = build_snapshot(TUPLES, NAMESPACES, K=8, version=12345)
        path = str(tmp_path / "mirror.npz")
        save_snapshot(snap, path)
        loaded = load_snapshot(path)
        assert loaded is not None
        assert loaded.version == 12345
        assert loaded.ns_ids == snap.ns_ids
        assert loaded.rel_ids == snap.rel_ids
        assert loaded.obj_slots == snap.obj_slots
        assert loaded.subj_ids == snap.subj_ids
        assert loaded.n_config_rels == snap.n_config_rels
        assert loaded.dh_probes == snap.dh_probes
        for k in ("dh_obj", "dh_sa", "rh_row", "row_ptr", "e_obj",
                  "instr_kind", "prog_flags", "objslot_ns"):
            np.testing.assert_array_equal(getattr(loaded, k), getattr(snap, k))

    def test_missing_and_corrupt_files(self, tmp_path):
        assert load_snapshot(str(tmp_path / "absent.npz")) is None
        bad = tmp_path / "bad.npz"
        bad.write_bytes(b"not a zip archive")
        assert load_snapshot(str(bad)) is None


class TestEngineWarmRestart:
    def _config(self, tmp_path):
        cfg = Config({"check": {"mirror_cache": str(tmp_path)}})
        cfg.set_namespaces(NAMESPACES)
        return cfg

    def test_second_engine_loads_from_cache(self, tmp_path):
        m = MemoryManager()
        m.write_relation_tuples(TUPLES)
        e1 = TPUCheckEngine(m, self._config(tmp_path))
        assert e1.check_is_member(ts("files:a#view@bob")[0])
        assert e1.stats["snapshot_builds"] == 1
        e1.flush_checkpoints()  # persistence is deferred off the check path

        # "restart": fresh engine over the same store + cache dir
        e2 = TPUCheckEngine(m, self._config(tmp_path))
        assert e2.check_is_member(ts("files:a#view@bob")[0])
        assert not e2.check_is_member(ts("files:a#view@eve")[0])
        assert e2.stats["snapshot_builds"] == 0
        assert e2.stats.get("snapshot_loads") == 1

    def test_stale_cache_ignored(self, tmp_path):
        m = MemoryManager()
        m.write_relation_tuples(TUPLES)
        e1 = TPUCheckEngine(m, self._config(tmp_path))
        e1.check_is_member(ts("files:a#view@bob")[0])
        e1.flush_checkpoints()

        # the store moves beyond the checkpointed version; a fresh engine
        # cannot prove delta coverage from version 0, so it rebuilds
        m.write_relation_tuples(ts("files:new#owner@zoe"))
        e2 = TPUCheckEngine(m, self._config(tmp_path))
        assert e2.check_is_member(ts("files:new#owner@zoe")[0])
        assert e2.stats["snapshot_builds"] == 1

    def test_config_change_invalidates_cache(self, tmp_path):
        m = MemoryManager()
        m.write_relation_tuples(TUPLES)
        e1 = TPUCheckEngine(m, self._config(tmp_path))
        e1.check_is_member(ts("files:a#view@bob")[0])
        e1.flush_checkpoints()

        cfg2 = Config({"check": {"mirror_cache": str(tmp_path)}})
        cfg2.set_namespaces([Namespace(name="files", relations=[Relation(name="owner")])])
        e2 = TPUCheckEngine(m, cfg2)
        e2.check_batch(ts("files:a#owner@alice"))
        assert e2.stats["snapshot_builds"] == 1
        assert e2.stats.get("snapshot_loads") is None

    def test_cache_refreshes_after_rebuild(self, tmp_path):
        m = MemoryManager()
        m.write_relation_tuples(TUPLES)
        e1 = TPUCheckEngine(m, self._config(tmp_path))
        e1.check_is_member(ts("files:a#view@bob")[0])
        e1.flush_checkpoints()
        m.write_relation_tuples(ts("files:new#owner@zoe"))
        e2 = TPUCheckEngine(m, self._config(tmp_path))
        e2.check_is_member(ts("files:new#owner@zoe")[0])  # rebuild + save
        e2.flush_checkpoints()
        e3 = TPUCheckEngine(m, self._config(tmp_path))
        assert e3.check_is_member(ts("files:new#owner@zoe")[0])
        assert e3.stats.get("snapshot_loads") == 1


class TestArrayVocabReload:
    def test_big_vocab_reloads_as_arraymap(self, tmp_path, monkeypatch):
        """Past the size threshold, vocabularies reload as ArrayMaps
        (sorted keys + explicit values) — identical lookups, no giant
        Python dicts on the warm-restart path."""
        from keto_tpu.engine import checkpoint as cp
        from keto_tpu.engine.snapshot import ArrayMap, build_snapshot

        tuples = ts(*[f"files:o{i}#view@u{i % 13}" for i in range(64)])
        snap = build_snapshot(tuples, NAMESPACES)
        path = str(tmp_path / "m.npz")
        cp.save_snapshot(snap, path)

        monkeypatch.setattr(cp, "_ARRAY_VOCAB_THRESHOLD", 4)
        loaded = cp.load_snapshot(path)
        assert isinstance(loaded.obj_slots, ArrayMap)
        assert isinstance(loaded.subj_ids, ArrayMap)
        # exact same id assignment as the saved (dict-built) snapshot
        for key, slot in snap.obj_slots.items():
            assert loaded.obj_slots.get(key) == slot
        for key, sid in snap.subj_ids.items():
            assert loaded.subj_ids.get(key) == sid
        assert len(loaded.obj_slots) == len(snap.obj_slots)


class TestTornCheckpointFiles:
    """Crash-ordering fallout: a checkpoint file torn at any byte must
    degrade to a rebuild (load returns None), never raise through
    engine construction or Daemon.start."""

    def _saved(self, tmp_path):
        snap = build_snapshot(TUPLES, NAMESPACES, K=8, version=99)
        path = str(tmp_path / "mirror-default.npz")
        save_snapshot(snap, path)
        return path

    def test_truncated_file_falls_back(self, tmp_path):
        path = self._saved(tmp_path)
        data = open(path, "rb").read()
        for frac in (0.25, 0.6, 0.95):
            open(path, "wb").write(data[: int(len(data) * frac)])
            assert load_snapshot(path) is None

    def test_bitrot_member_data_falls_back(self, tmp_path):
        """In-place corruption of the `meta` member's deflate stream
        (bit rot: zip structure intact, data garbage) raises zlib.error
        from the decompressor — also in the degrade set, never through
        Daemon.start's recovery audit or the check path."""
        import zipfile

        from keto_tpu.engine.checkpoint import checkpoint_info

        path = self._saved(tmp_path)
        with zipfile.ZipFile(path) as zf:
            info = zf.getinfo("meta.npy")
        data = bytearray(open(path, "rb").read())
        # local file header: 30 fixed bytes + name + extra, then the
        # compressed stream — flip bytes squarely inside it
        name_len = int.from_bytes(
            data[info.header_offset + 26:info.header_offset + 28], "little"
        )
        extra_len = int.from_bytes(
            data[info.header_offset + 28:info.header_offset + 30], "little"
        )
        start = info.header_offset + 30 + name_len + extra_len
        for off in range(start, start + max(info.compress_size - 1, 1)):
            data[off] ^= 0xFF
        open(path, "wb").write(bytes(data))
        assert load_snapshot(path) is None
        assert checkpoint_info(path)["loadable"] is False

    def test_wrong_format_version_falls_back(self, tmp_path, monkeypatch):
        from keto_tpu.engine import checkpoint as cp

        monkeypatch.setattr(cp, "FORMAT_VERSION", 999)
        path = self._saved(tmp_path)
        monkeypatch.undo()
        assert load_snapshot(path) is None
        info = cp.checkpoint_info(path)
        assert info is not None and info["loadable"] is False

    def test_checkpoint_info_probe(self, tmp_path):
        from keto_tpu.engine.checkpoint import checkpoint_info

        assert checkpoint_info(str(tmp_path / "absent.npz")) is None
        path = self._saved(tmp_path)
        info = checkpoint_info(path)
        assert info["loadable"] is True
        assert info["n_tuples"] == len(TUPLES)
        bad = tmp_path / "garbage.npz"
        bad.write_bytes(b"\x00" * 64)
        assert checkpoint_info(str(bad))["loadable"] is False

    def test_engine_counts_corrupt_fallback_and_recovers(self, tmp_path):
        from keto_tpu.observability import Metrics

        m = MemoryManager()
        m.write_relation_tuples(TUPLES)
        (tmp_path / "mirror-default.npz").write_bytes(b"not a zip")
        cfg = Config({"check": {"mirror_cache": str(tmp_path)}})
        cfg.set_namespaces(NAMESPACES)
        e = TPUCheckEngine(m, cfg, metrics=Metrics())
        assert e.check_is_member(ts("files:a#view@bob")[0])
        assert e.stats["snapshot_builds"] == 1
        assert e.stats.get("checkpoint_fallback_corrupt") == 1
        assert (
            e.metrics.checkpoint_load_fallbacks_total.labels("corrupt")
            ._value.get() == 1
        )

    def test_engine_counts_stale_fallback(self, tmp_path):
        m = MemoryManager()
        m.write_relation_tuples(TUPLES)
        cfg = Config({"check": {"mirror_cache": str(tmp_path)}})
        cfg.set_namespaces(NAMESPACES)
        e1 = TPUCheckEngine(m, cfg)
        e1.check_is_member(ts("files:a#view@bob")[0])
        e1.flush_checkpoints()
        m.write_relation_tuples(ts("files:new#owner@zoe"))
        e2 = TPUCheckEngine(m, cfg)
        assert e2.check_is_member(ts("files:new#owner@zoe")[0])
        assert e2.stats.get("checkpoint_fallback_stale") == 1

    def test_daemon_starts_over_torn_checkpoint(self, tmp_path):
        """The Daemon.start contract the satellite pins: a torn mirror
        file yields the recovery-audit log line and a rebuild, never an
        exception through startup."""
        from keto_tpu.api.daemon import Daemon
        from keto_tpu.registry import Registry

        (tmp_path / "mirror-default.npz").write_bytes(b"\x1f\x8b torn")
        cfg = Config({
            "dsn": "memory",
            "check": {"engine": "host", "mirror_cache": str(tmp_path)},
            "serve": {
                "read": {"host": "127.0.0.1", "port": 0},
                "write": {"host": "127.0.0.1", "port": 0},
                "metrics": {"host": "127.0.0.1", "port": 0},
            },
        })
        cfg.set_namespaces(NAMESPACES)
        d = Daemon(Registry(cfg))
        d.start()
        try:
            assert d.registry.ready.is_set()
        finally:
            d.stop()


class TestSaveSnapshotDurability:
    def test_fsyncs_temp_file_before_rename(self, tmp_path, monkeypatch):
        """The crash-ordering contract: the temp file's bytes reach disk
        (fsync) BEFORE os.replace publishes its name."""
        import os as real_os

        events = []
        real_fsync, real_replace = real_os.fsync, real_os.replace
        monkeypatch.setattr(
            real_os, "fsync",
            lambda fd: (events.append("fsync"), real_fsync(fd))[1],
        )
        monkeypatch.setattr(
            real_os, "replace",
            lambda a, b: (events.append("replace"), real_replace(a, b))[1],
        )
        snap = build_snapshot(TUPLES, NAMESPACES, K=8, version=5)
        save_snapshot(snap, str(tmp_path / "m.npz"))
        assert "fsync" in events and "replace" in events
        assert events.index("fsync") < events.index("replace")

    def test_no_temp_left_on_success(self, tmp_path):
        snap = build_snapshot(TUPLES, NAMESPACES, K=8, version=5)
        save_snapshot(snap, str(tmp_path / "m.npz"))
        assert [f for f in tmp_path.iterdir() if f.name.endswith(".tmp")] == []


class TestFlushFailureTolerance:
    """registry.flush_checkpoints: a checkpoint write error during
    shutdown must not abort the drain (satellite pin)."""

    def _registry(self):
        from keto_tpu.registry import Registry

        cfg = Config({"dsn": "memory"})
        cfg.set_namespaces(NAMESPACES)
        reg = Registry(cfg)
        reg.relation_tuple_manager().write_relation_tuples(TUPLES)
        return reg

    def test_deferred_flush_oserror_counted_at_engine(self):
        """The REAL failure mode: save_snapshot raising OSError inside
        the engine's deferred flush (which swallows it to keep serving)
        must still advance the write-failures counter — the registry's
        shutdown catch never sees this path."""
        from keto_tpu.observability import Metrics

        m = MemoryManager()
        m.write_relation_tuples(TUPLES)
        import pathlib

        def engine_for(tmp):
            cfg = Config({"check": {"mirror_cache": str(tmp)}})
            cfg.set_namespaces(NAMESPACES)
            return TPUCheckEngine(m, cfg, metrics=Metrics())

        import tempfile

        with tempfile.TemporaryDirectory() as d:
            notadir = pathlib.Path(d) / "notadir"
            notadir.write_bytes(b"")  # a FILE where the cache dir must be
            e = engine_for(notadir)
            e.check_is_member(ts("files:a#owner@alice")[0])
            e.flush_checkpoints()  # save fails (FileExistsError ⊂ OSError)
            # the zero-delay persist TIMER may have claimed the pending
            # snapshot before the explicit flush; its failing save counts
            # on the timer thread — wait for it rather than racing it
            import time as _time

            counter = e.metrics.checkpoint_write_failures_total
            deadline = _time.monotonic() + 5
            while _time.monotonic() < deadline and counter._value.get() < 1:
                _time.sleep(0.01)
            assert counter._value.get() == 1

    def test_flush_error_logged_counted_not_raised(self):
        reg = self._registry()
        engine = reg.check_engine()

        def boom():
            raise RuntimeError("disk on fire")

        engine.flush_checkpoints = boom
        reg.flush_checkpoints()  # must not raise
        assert (
            reg.metrics().checkpoint_write_failures_total._value.get() == 1
        )

    def test_daemon_stop_survives_flush_failure(self):
        from keto_tpu.api.daemon import Daemon
        from keto_tpu.registry import Registry

        cfg = Config({
            "dsn": "memory",
            "check": {"engine": "host"},
            "serve": {
                "read": {"host": "127.0.0.1", "port": 0},
                "write": {"host": "127.0.0.1", "port": 0},
                "metrics": {"host": "127.0.0.1", "port": 0},
            },
        })
        cfg.set_namespaces(NAMESPACES)
        d = Daemon(Registry(cfg))
        d.start()
        engine = d.registry.check_engine()

        def boom():
            raise OSError("readonly filesystem")

        engine.flush_checkpoints = boom
        d.stop()  # must complete the drain despite the failing flush
        assert (
            d.registry.metrics().checkpoint_write_failures_total
            ._value.get() == 1
        )


class TestStrictRestore:
    """PR 20: restore_snapshot is the HA follower's cold-start path —
    torn files degrade to None (rebuild via bootstrap), but a file that
    is INTACT yet unreadable by this process (format bump, cross-layout
    cache dir) raises the typed CheckpointIncompatibleError instead of
    silently rebuilding over an operational mistake."""

    def _saved(self, tmp_path):
        snap = build_snapshot(TUPLES, NAMESPACES, K=8, version=99)
        path = str(tmp_path / "mirror-default.npz")
        save_snapshot(snap, path)
        return path

    def test_intact_file_restores(self, tmp_path):
        from keto_tpu.engine.checkpoint import restore_snapshot

        snap = restore_snapshot(self._saved(tmp_path))
        assert snap is not None and snap.version == 99

    def test_missing_file_is_none(self, tmp_path):
        from keto_tpu.engine.checkpoint import restore_snapshot

        assert restore_snapshot(str(tmp_path / "absent.npz")) is None

    def test_torn_file_is_none_not_raise(self, tmp_path):
        from keto_tpu.engine.checkpoint import restore_snapshot

        path = self._saved(tmp_path)
        data = open(path, "rb").read()
        for frac in (0.25, 0.6, 0.95):
            open(path, "wb").write(data[: int(len(data) * frac)])
            assert restore_snapshot(path) is None

    def test_garbage_file_is_none(self, tmp_path):
        from keto_tpu.engine.checkpoint import restore_snapshot

        bad = tmp_path / "garbage.npz"
        bad.write_bytes(b"\x00" * 64)
        assert restore_snapshot(str(bad)) is None

    def test_format_version_mismatch_raises_typed(self, tmp_path, monkeypatch):
        from keto_tpu.engine import checkpoint as cp
        from keto_tpu.errors import CheckpointIncompatibleError

        monkeypatch.setattr(cp, "FORMAT_VERSION", 999)
        path = self._saved(tmp_path)
        monkeypatch.undo()
        with pytest.raises(CheckpointIncompatibleError) as ei:
            cp.restore_snapshot(path)
        assert "format" in str(ei.value.debug)

    def test_cross_layout_raises_typed(self, tmp_path):
        # A v4 file as a CPU process published it before the layouts were
        # merged: its tables would mis-answer under today's probe
        # sequence, so the audit says not loadable, the restore by name is
        # refused with the typed error (not a crash, not a silent
        # rebuild), and the engine's own probe degrades to a rebuild.
        from keto_tpu.engine import checkpoint as cp
        from keto_tpu.errors import CheckpointIncompatibleError

        m = MemoryManager()
        m.write_relation_tuples(TUPLES)
        cfg = Config({"check": {"mirror_cache": str(tmp_path)}})
        cfg.set_namespaces(NAMESPACES)
        e1 = TPUCheckEngine(m, cfg)
        assert e1.check_is_member(ts("files:a#view@bob")[0])
        e1.flush_checkpoints()
        path = cp.mirror_cache_path(str(tmp_path), "default")
        assert cp.FORMAT_VERSION == 4
        assert cp.checkpoint_info(path)["loadable"] is True
        forge_retired_layout(path)

        info = cp.checkpoint_info(path)
        assert info["loadable"] is False and info["table_layout"] == "compact"
        with pytest.raises(CheckpointIncompatibleError) as ei:
            cp.restore_snapshot(path)
        assert "'compact' table layout" in str(ei.value.debug)
        assert cp.load_snapshot(path) is None
        e2 = TPUCheckEngine(m, cfg)
        assert e2.check_is_member(ts("files:a#view@bob")[0])
        assert e2.stats["snapshot_builds"] == 1
        assert e2.stats.get("snapshot_loads") is None
        assert e2.stats.get("checkpoint_fallback_corrupt") == 1
