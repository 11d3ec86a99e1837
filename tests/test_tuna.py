"""repro.tuna schedule database + orchestrator + warm-cache integration.

Covers the subsystem contract: cm1 round-trip persistence, best-record
queries, compaction, parallel fan-out, and — the acceptance criterion — a
second ``tuner.tune`` against a warm DB returning the identical best config
with **zero** cost-model evaluations. Plus the cm1 golden: the feature
vector and score of one pinned schedule, so cost-model refactors must bump
``COST_MODEL_VERSION`` instead of silently invalidating stored records.
"""
import json

import numpy as np
import pytest

from repro.core import cost_model, tuner
from repro.core.cost_model import COST_MODEL_VERSION
from repro.core.spaces import BatchMatmulSpace, MatmulSpace
from repro.hw import get_target
from repro.tuna import orchestrator
from repro.tuna.db import ScheduleDatabase, ScheduleRecord

TPU = get_target("tpu_v5e")


def _rec(op="matmul[K=256,M=256,N=256,dtype_bytes=2]", target="tpu_v5e",
         score=1.0, **kw):
    return ScheduleRecord(op=op, target=target,
                          config={"bm": 256, "bn": 256, "bk": 256},
                          score=score, **kw)


class TestScheduleDatabase:
    def test_roundtrip_write_reload_query_best(self, tmp_path):
        path = tmp_path / "db.jsonl"
        db = ScheduleDatabase(path)
        db.add(_rec(score=2.0))
        db.add(_rec(score=1.0))            # improves
        db.add(_rec(score=5.0))            # worse: logged, not indexed
        db.add(_rec(op="other[]", score=3.0))

        re = ScheduleDatabase(path)
        assert re.lines_read == 4 and len(re) == 2
        best = re.best("matmul[K=256,M=256,N=256,dtype_bytes=2]", "tpu_v5e")
        assert best is not None and best.score == 1.0
        assert best.config == {"bm": 256, "bn": 256, "bk": 256}
        assert best.version == COST_MODEL_VERSION
        # version is part of the key: other cost-model versions don't match
        assert re.best("other[]", "tpu_v5e", version="cm0") is None

    def test_corrupt_lines_skipped(self, tmp_path):
        path = tmp_path / "db.jsonl"
        ScheduleDatabase(path).add(_rec(score=1.5))
        with open(path, "a") as f:
            f.write("{not json\n\n")
            f.write(json.dumps({"op": "x"}) + "\n")  # missing fields
        re = ScheduleDatabase(path)
        assert re.corrupt_lines == 2 and len(re) == 1

    def test_compact_drops_superseded_lines(self, tmp_path):
        path = tmp_path / "db.jsonl"
        db = ScheduleDatabase(path)
        for s in (4.0, 3.0, 2.0, 1.0):
            db.add(_rec(score=s))
        db.add(_rec(op="other[]", score=9.0))
        assert db.compact() == 3
        re = ScheduleDatabase(path)
        assert re.lines_read == 2 and len(re) == 2
        assert re.best("matmul[K=256,M=256,N=256,dtype_bytes=2]",
                       "tpu_v5e").score == 1.0

    def test_merge_and_export(self, tmp_path):
        a = ScheduleDatabase(tmp_path / "a.jsonl")
        a.add(_rec(score=2.0))
        b = ScheduleDatabase(tmp_path / "b.jsonl")
        b.add(_rec(score=1.0))                 # beats a's record
        b.add(_rec(op="other[]", score=7.0))   # new key
        b.add(_rec(score=3.0))                 # worse: not absorbed
        assert a.merge(str(tmp_path / "b.jsonl")) == 2
        assert a.best("matmul[K=256,M=256,N=256,dtype_bytes=2]",
                      "tpu_v5e").score == 1.0
        out = tmp_path / "out.json"
        assert a.export(str(out)) == 2
        assert len(json.loads(out.read_text())) == 2

    def test_query_prefix_and_filters(self, tmp_path):
        db = ScheduleDatabase()
        db.add(_rec(score=1.0))
        db.add(_rec(op="matmul[K=512,M=512,N=512,dtype_bytes=2]", score=2.0))
        db.add(_rec(op="conv2d[...]", target="cpu_avx2", score=3.0))
        assert len(db.query(op="matmul")) == 2
        assert len(db.query(target="cpu_avx2")) == 1
        assert len(db.query()) == 3


class TestSignature:
    def test_matches_legacy_record_format(self):
        s = MatmulSpace(4096, 4096, 4096, 2, target_kind="tpu")
        assert s.signature() == "matmul[K=4096,M=4096,N=4096,dtype_bytes=2]"
        b = BatchMatmulSpace(8, 128, 128, 64, 4, target_kind="tpu")
        assert b.signature() == "batch_matmul[Bsz=8,K=64,M=128,N=128,dtype_bytes=4]"

    def test_target_kind_not_in_signature(self):
        tpu = MatmulSpace(256, 256, 256, 4, target_kind="tpu")
        cpu = MatmulSpace(256, 256, 256, 4, target_kind="cpu")
        assert tpu.signature() == cpu.signature()


class TestWarmCache:
    def test_tune_zero_evaluations_on_warm_db(self, tmp_path, monkeypatch):
        """Acceptance: populate once, then an identical tune performs zero
        cost-model evaluations and returns the identical best config."""
        path = str(tmp_path / "db.jsonl")
        space = MatmulSpace(1024, 1024, 1024, 2, target_kind="tpu")
        cold = tuner.tune(space, TPU, db=path)
        assert not cold.from_db and cold.evaluations > 0

        calls = []
        real = cost_model.evaluate

        def counting(*a, **kw):
            calls.append(1)
            return real(*a, **kw)

        monkeypatch.setattr(cost_model, "evaluate", counting)
        warm = tuner.tune(MatmulSpace(1024, 1024, 1024, 2, "tpu"), TPU,
                          db=path)
        assert warm.from_db
        assert warm.evaluations == 0 and not calls
        assert warm.config == cold.config
        assert warm.score == cold.score

    def test_tune_zero_evaluations_from_snapshot_cache(self, tmp_path,
                                                       monkeypatch):
        """Acceptance: a warm hit served through ``ScheduleCache`` alone
        (no DB installed at all) performs zero cost-model evaluations."""
        from repro.tuna.cache import ScheduleCache

        path = str(tmp_path / "db.jsonl")
        space = MatmulSpace(1024, 1024, 1024, 2, target_kind="tpu")
        cold = tuner.tune(space, TPU, db=path)
        snap = str(tmp_path / "cache.json")
        ScheduleCache.build(path, snap)

        tuner.set_default_db(None)  # the snapshot serves on its own
        tuner.set_default_cache(snap)

        def boom(*a, **kw):
            raise AssertionError("cost model evaluated despite snapshot")

        monkeypatch.setattr(cost_model, "evaluate", boom)
        warm = tuner.tune(MatmulSpace(1024, 1024, 1024, 2, "tpu"), TPU)
        assert warm.from_db and warm.from_cache
        assert warm.evaluations == 0
        assert warm.config == cold.config and warm.score == cold.score
        assert tuner.get_default_cache().hits >= 1
        # and the snapshot never absorbs write-backs
        with pytest.raises(TypeError):
            tuner.get_default_cache().add(None)

    def test_env_cache_pointing_at_unbuilt_snapshot_is_off(self, tmp_path,
                                                           monkeypatch):
        """$REPRO_TUNA_CACHE naming a snapshot that hasn't been built yet
        must resolve to 'no cache', not crash every lookup."""
        monkeypatch.setenv("REPRO_TUNA_CACHE",
                           str(tmp_path / "not_built_yet.json"))
        monkeypatch.setattr(tuner, "_DEFAULT_CACHE", tuner._UNSET)
        assert tuner.get_default_cache() is None
        res = tuner.tune(MatmulSpace(256, 256, 256, 2, "tpu"), TPU, db=False)
        assert not res.from_db and res.evaluations > 0

    def test_flash_blocks_served_from_snapshot_cache(self, tmp_path,
                                                     monkeypatch):
        from repro.kernels import ops
        from repro.tuna.cache import ScheduleCache

        db = ScheduleDatabase(tmp_path / "db.jsonl")
        db.add(ScheduleRecord(
            op="flash[d=128,dtype_bytes=2,s=2048]", target="tpu_v5e",
            config={"block_q": 256, "block_k": 128}, score=1e-9))
        snap = str(tmp_path / "cache.json")
        ScheduleCache.build(db.path, snap)
        ops.use_schedule_cache(snap)  # clears the memo, installs the cache
        assert ops.tuned_flash_blocks(2048, 128, 2, "tpu_v5e") == (256, 128)
        assert tuner.get_default_cache().hits >= 1

    def test_tuned_matmul_blocks_served_from_default_db(self, tmp_path,
                                                        monkeypatch):
        path = str(tmp_path / "db.jsonl")
        space = MatmulSpace(2048, 2048, 2048, 2, target_kind="tpu")
        cfg, _ = tuner.best_schedule(space, TPU, db=path)

        tuner.set_default_db(path)  # also clears the lru memo

        def boom(*a, **kw):
            raise AssertionError("cost model evaluated despite warm DB")

        monkeypatch.setattr(cost_model, "evaluate", boom)
        bm, bn, bk = tuner.tuned_matmul_blocks(2048, 2048, 2048, 2, "tpu_v5e")
        assert (bm, bn, bk) == (cfg["bm"], cfg["bn"], cfg["bk"])

    def test_rank_space_writes_back_best(self, tmp_path):
        db = ScheduleDatabase(tmp_path / "db.jsonl")
        space = MatmulSpace(512, 512, 512, 2, target_kind="tpu")
        ranked = tuner.rank_space(space, TPU, limit=1024, db=db)
        rec = db.best(space.signature(), "tpu_v5e")
        assert rec is not None
        assert rec.config == ranked[0][0] and rec.score == ranked[0][1]
        assert rec.meta["strategy"] == "exhaustive"
        # the centre config was enumerated, so its score is recorded and a
        # warm tune() can report a real default_score
        assert rec.meta["default_score"] == pytest.approx(
            dict((tuple(sorted(c.items())), s) for c, s in ranked)[
                tuple(sorted(space.default_config().items()))])

    def test_env_var_fallback_and_explicit_off(self, tmp_path, monkeypatch):
        path = str(tmp_path / "db.jsonl")
        tuner.tune(MatmulSpace(256, 256, 256, 2, "tpu"), TPU, db=path)
        monkeypatch.setenv("REPRO_TUNA_DB", path)
        monkeypatch.setattr(tuner, "_DEFAULT_DB", tuner._UNSET)
        warm = tuner.tune(MatmulSpace(256, 256, 256, 2, "tpu"), TPU)
        assert warm.from_db
        # explicit None switches the default off despite the env var
        tuner.set_default_db(None)
        assert tuner.get_default_db() is None

    def test_set_default_db_clears_flash_memo(self, tmp_path):
        from repro.kernels import ops

        heuristic = ops.tuned_flash_blocks(1024, 128, 2, "tpu_v5e")  # memoised, no DB
        db = ScheduleDatabase(tmp_path / "db.jsonl")
        db.add(ScheduleRecord(
            op="flash[d=128,dtype_bytes=2,s=1024]", target="tpu_v5e",
            config={"block_q": 128, "block_k": 128}, score=1e-9))
        tuner.set_default_db(db)
        assert ops.tuned_flash_blocks(1024, 128, 2, "tpu_v5e") == (128, 128)
        assert heuristic != (128, 128)  # proves the memo was refreshed


class TestOrchestrator:
    def test_fanout_two_spaces_pool_of_two(self, tmp_path):
        db = ScheduleDatabase(tmp_path / "db.jsonl")
        jobs = orchestrator.jobs_for(
            ["dense_256", "batch_matmul"], ["tpu_v5e"], limit=256)
        report = orchestrator.run(jobs, db=db, workers=2)
        assert report.ok and len(report.records) == 2
        assert len(db) == 2
        # results must equal what an in-process exhaustive search finds
        for job in jobs:
            space = orchestrator.build_space(job)
            expect_cfg, expect_score = tuner.rank_space(
                space, TPU, limit=256)[0]
            rec = db.best(space.signature(), "tpu_v5e")
            assert rec.config == expect_cfg
            assert rec.score == pytest.approx(expect_score)
        # persisted: a fresh reload serves the same records
        re = ScheduleDatabase(tmp_path / "db.jsonl")
        assert len(re) == 2

    def test_failures_reported_after_retries(self, tmp_path):
        db = ScheduleDatabase()
        jobs = [orchestrator.TuneJob(op="no_such_op", target="tpu_v5e"),
                orchestrator.TuneJob(op="dense_256", target="tpu_v5e",
                                     limit=64)]
        report = orchestrator.run(jobs, db=db, workers=1, retries=1)
        assert len(report.records) == 1 and len(report.failures) == 1
        fail = report.failures[0]
        assert fail.job.op == "no_such_op" and fail.attempts == 2
        assert "no_such_op" in fail.error


class TestCli:
    def test_smoke_tune_query_compact_export(self, tmp_path, capsys):
        from repro.tuna import cli

        db = str(tmp_path / "db.jsonl")
        assert cli.main(["tune", "--smoke", "--db", db, "--workers", "1"]) == 0
        assert cli.main(["query", "--db", db, "--target", "tpu_v5e"]) == 0
        out = capsys.readouterr().out
        assert "matmul[K=256,M=256,N=256,dtype_bytes=4]" in out
        assert cli.main(["compact", "--db", db]) == 0
        assert cli.main(["export", "--db", db,
                         "--out", str(tmp_path / "out.json")]) == 0
        assert cli.main(["query", "--db", db, "--op", "nope["]) == 1

    def test_unknown_op_rejected(self, tmp_path):
        from repro.tuna import cli

        rc = cli.main(["tune", "--db", str(tmp_path / "db.jsonl"),
                       "--ops", "bogus", "--targets", "tpu_v5e"])
        assert rc == 2


class TestGoldenCostModel:
    """Pin the cm1 feature vector + score of one fixed schedule. If this
    fails, the cost model changed meaning: bump COST_MODEL_VERSION (stored
    cm1 records are then ignored, not silently mis-scored) and re-pin."""

    GOLDEN_FEATURES = {
        "ilp_cycles": 51623.48146520146,
        "movement_bytes": 1572864.0,
        "unhidden_dma_cycles": 5537.469108669109,
        "arith_ops": 64.0,
        "ldst_ops": 0.0,
        "alignment_waste": 0.0,
        "occupancy_penalty": 0.0,
        "vmem_overflow": 0.0,
        "parallel_extent": 16,
        "dispatch_calls": 64.0,
    }
    GOLDEN_SCORE = 6.114623058737953e-05

    def test_version_is_cm1(self):
        assert COST_MODEL_VERSION == "cm1"

    def test_feature_vector_and_score_pinned(self):
        space = MatmulSpace(512, 512, 512, 2, target_kind="tpu")
        cfg = {"bm": 128, "bn": 128, "bk": 128, "double_buffer": True}
        prog, meta = space.instantiate(cfg)
        feats = cost_model.extract_features(prog, TPU, meta)
        got = feats.as_dict()
        assert set(got) == set(self.GOLDEN_FEATURES)
        for name, want in self.GOLDEN_FEATURES.items():
            assert got[name] == pytest.approx(want, rel=1e-9), name
        assert cost_model.score(feats, TPU) == pytest.approx(
            self.GOLDEN_SCORE, rel=1e-9)
