"""Golden releases: regression gate, waivers, AOT kernel bundles, serve
parity, and the compact/export whole-store guards that make blessing a
release trustworthy."""
import dataclasses
import json
import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import tuner
from repro.kernels import ops, ref
from repro.tuna import cli
from repro.tuna.cache import (ScheduleCache, StaleSnapshotError,
                              StaleSnapshotWarning)
from repro.tuna.db import ScheduleDatabase, ScheduleRecord
from repro.tuna.golden import (
    BundleError,
    GoldenError,
    GoldenManager,
    GoldenRegressionError,
    KernelBundle,
    build_kernel_bundle,
    plan_bundle_entries,
)
from repro.tuna.transport import MemoryTransport

MM_OP = "matmul[K=128,M=128,N=128,dtype_bytes=4]"
FL_OP = "flash[d=64,dtype_bytes=4,s=128]"
TGT = "tpu_v5e"
RNG = np.random.default_rng(3)


def mk_records(mm_score=1e-6, fl_score=2e-6, with_flash=True,
               with_conv=True):
    recs = [ScheduleRecord(op=MM_OP, target=TGT, score=mm_score,
                           config={"bm": 64, "bn": 64, "bk": 64})]
    if with_flash:
        recs.append(ScheduleRecord(op=FL_OP, target=TGT, score=fl_score,
                                   config={"block_q": 64, "block_k": 64}))
    if with_conv:
        # rides in the schedule index but has no Pallas kernel to AOT
        recs.append(ScheduleRecord(op="conv2d[foo=1]", target=TGT,
                                   config={"x": 1}, score=3e-6))
    return recs


def _mem(tmp_path) -> MemoryTransport:
    bucket = f"golden-{os.path.basename(tmp_path)}"
    MemoryTransport.wipe(bucket)
    return MemoryTransport(bucket)


class TestGoldenLifecycle:
    def test_promote_reload_and_noop_repromote(self, tmp_path):
        mgr = GoldenManager(str(tmp_path))
        info = mgr.promote(mk_records(), TGT, source="unit")
        assert info.rebuilt and info.repointed
        assert info.predecessor is None and info.count == 3
        assert os.path.exists(info.path) and os.path.exists(info.latest)
        hdr, records = mgr.load_release(info.latest)  # follows the pointer
        assert hdr["sha1"] == info.sha1 and len(records) == 3
        assert hdr["source"] == "unit"
        again = mgr.promote(mk_records(), TGT)
        assert not again.rebuilt and not again.repointed
        assert again.name == info.name

    def test_improvement_promotes_and_links_predecessor(self, tmp_path):
        mgr = GoldenManager(str(tmp_path))
        first = mgr.promote(mk_records(mm_score=2e-6), TGT)
        second = mgr.promote(mk_records(mm_score=1e-6), TGT)
        assert second.rebuilt and second.name != first.name
        assert second.predecessor == first.name
        assert second.gated_against == 3
        hdr, _ = mgr.load_release(second.path)
        assert hdr["predecessor"] == first.name
        assert mgr.current(TGT)["release"] == second.name

    def test_gate_refuses_slower_schedule(self, tmp_path):
        mgr = GoldenManager(str(tmp_path))
        first = mgr.promote(mk_records(mm_score=1e-6), TGT)
        with pytest.raises(GoldenRegressionError) as ei:
            mgr.promote(mk_records(mm_score=5e-6), TGT)
        (reg,) = ei.value.regressions
        assert reg.kind == "slower" and reg.op == MM_OP
        assert reg.old_score == 1e-6 and reg.new_score == 5e-6
        # refused promotion must leave the blessed pointer untouched
        assert mgr.current(TGT)["release"] == first.name

    def test_gate_refuses_lost_coverage(self, tmp_path):
        mgr = GoldenManager(str(tmp_path))
        mgr.promote(mk_records(), TGT)
        with pytest.raises(GoldenRegressionError) as ei:
            mgr.promote(mk_records(with_flash=False), TGT)
        (reg,) = ei.value.regressions
        assert reg.kind == "lost" and reg.op == FL_OP

    def test_waiver_promotes_and_is_recorded(self, tmp_path):
        mgr = GoldenManager(str(tmp_path))
        mgr.promote(mk_records(mm_score=1e-6), TGT)
        spec = f"{MM_OP}@{TGT}"
        info = mgr.promote(mk_records(mm_score=5e-6), TGT, waive=[spec])
        assert len(info.waived) == 1 and info.waived[0].waived_by == spec
        hdr, _ = mgr.load_release(info.path)
        (w,) = hdr["waivers"]  # the audit trail the ISSUE demands
        assert w["waived_by"] == spec and w["kind"] == "slower"
        assert w["old_score"] == 1e-6 and w["new_score"] == 5e-6

    def test_waiver_does_not_cover_other_regressions(self, tmp_path):
        mgr = GoldenManager(str(tmp_path))
        mgr.promote(mk_records(), TGT)
        with pytest.raises(GoldenRegressionError) as ei:
            mgr.promote(mk_records(mm_score=5e-6, with_flash=False), TGT,
                        waive=[f"{MM_OP}@{TGT}"])
        (reg,) = ei.value.regressions  # matmul waived, flash loss still blocks
        assert reg.op == FL_OP and reg.kind == "lost"

    def test_cost_model_bump_starts_fresh_lineage(self, tmp_path,
                                                  monkeypatch):
        mgr = GoldenManager(str(tmp_path))
        mgr.promote(mk_records(mm_score=1e-6), TGT)
        monkeypatch.setattr("repro.tuna.golden.COST_MODEL_VERSION", "cm99")
        recs = [dataclasses.replace(r, version="cm99")
                for r in mk_records(mm_score=9e-6)]
        info = mgr.promote(recs, TGT)  # slower, but scores aren't comparable
        assert info.predecessor is None and info.gated_against == 0
        assert ".cm99-" in info.name

    def test_corrupt_release_refused(self, tmp_path):
        mgr = GoldenManager(str(tmp_path))
        info = mgr.promote(mk_records(), TGT)
        obj = json.load(open(info.path))
        obj["records"][0]["score"] = 0.5  # tamper past the gate
        json.dump(obj, open(info.path, "w"))
        with pytest.raises(GoldenError, match="digest mismatch"):
            mgr.load_release(info.path)

    def test_nothing_to_promote(self, tmp_path):
        mgr = GoldenManager(str(tmp_path))
        with pytest.raises(GoldenError, match="nothing to promote"):
            mgr.promote(mk_records(), "tpu_v4")  # no records for the target


@pytest.fixture(scope="module")
def built_bundle(tmp_path_factory):
    """One promoted golden + AOT bundle shared by the read-only tests."""
    d = str(tmp_path_factory.mktemp("bundle"))
    mgr = GoldenManager(d)
    info = mgr.promote(mk_records(), TGT, source="fixture")
    _, release = mgr.load_release(info.path)
    binfo = build_kernel_bundle(release, d, TGT, golden_name=info.name)
    return mgr, info, binfo


class TestKernelBundle:
    def test_plan_partitions_records(self):
        plans, skipped = plan_bundle_entries(mk_records())
        assert sorted(p.kernel for p in plans) == ["flash", "matmul"]
        (skip,) = skipped
        assert skip[0] == "conv2d[foo=1]" and "no Pallas kernel" in skip[1]

    def test_build_load_execute(self, built_bundle):
        _, info, binfo = built_bundle
        assert binfo.entries == 2 and binfo.schedules == 3
        bundle = KernelBundle.load(binfo.path)
        assert len(bundle) == 2 and bundle.golden == info.name
        x = jnp.asarray(RNG.standard_normal((128, 128)), jnp.float32)
        y = jnp.asarray(RNG.standard_normal((128, 128)), jnp.float32)
        fn = bundle.executable("matmul", (x, y))
        assert fn is not None
        np.testing.assert_allclose(np.asarray(fn(x, y)),
                                   np.asarray(x) @ np.asarray(y),
                                   rtol=1e-5, atol=1e-4)
        q = jnp.asarray(RNG.standard_normal((1, 1, 128, 64)), jnp.float32)
        att = bundle.executable(
            "flash", (q, q, q), {"causal": True, "scale": 64 ** -0.5})
        assert att is not None
        np.testing.assert_allclose(
            np.asarray(att(q, q, q)),
            np.asarray(ref.attention(q, q, q, causal=True)),
            rtol=1e-5, atol=1e-4)
        assert bundle.exec_hits == 2
        # unknown shape -> graceful miss, caller traces normally
        small = jnp.ones((8, 8), jnp.float32)
        assert bundle.executable("matmul", (small, small)) is None
        assert bundle.exec_misses == 1

    def test_schedule_tier_and_immutability(self, built_bundle):
        _, _, binfo = built_bundle
        bundle = KernelBundle.load(binfo.path)
        rec = bundle.best(FL_OP, TGT)
        assert rec.config == {"block_q": 64, "block_k": 64}
        # the non-kernel record still rides in the schedule index
        assert bundle.best("conv2d[foo=1]", TGT) is not None
        assert bundle.best("nope[]", TGT) is None
        assert bundle.hits == 2 and bundle.misses == 1
        with pytest.raises(TypeError):
            bundle.add(None)

    def test_latest_pointer_followed(self, built_bundle):
        _, _, binfo = built_bundle
        via_ptr = KernelBundle.load(binfo.latest)
        assert via_ptr.sha1 == binfo.sha1

    def _tampered(self, binfo, tmp_path, **header_edits):
        obj = json.load(open(binfo.path))
        obj.update(header_edits)
        path = str(tmp_path / "tampered.json")
        json.dump(obj, open(path, "w"))
        return path

    def test_load_refuses_torn_copy(self, built_bundle, tmp_path):
        _, _, binfo = built_bundle
        obj = json.load(open(binfo.path))
        obj["schedules"][0]["score"] = 0.5  # payload edit breaks the digest
        path = str(tmp_path / "torn.json")
        json.dump(obj, open(path, "w"))
        with pytest.raises(BundleError, match="digest mismatch"):
            KernelBundle.load(path)

    def test_load_refuses_stale_cost_model(self, built_bundle, tmp_path):
        _, _, binfo = built_bundle
        path = self._tampered(binfo, tmp_path, cost_model_version="cm0")
        with pytest.raises(StaleSnapshotError):
            KernelBundle.load(path)

    def test_load_refuses_foreign_backend(self, built_bundle, tmp_path):
        _, _, binfo = built_bundle
        path = self._tampered(binfo, tmp_path, backend="tpu")
        with pytest.raises(BundleError, match="backend"):
            KernelBundle.load(path)

    def test_load_refuses_wrong_schema(self, built_bundle, tmp_path):
        _, info, _ = built_bundle
        with pytest.raises(BundleError, match="not a kernel bundle"):
            KernelBundle.load(info.path)  # a golden release, not a bundle


class TestBundleDispatch:
    def test_zero_trace_dispatch_with_numeric_parity(self, built_bundle):
        _, _, binfo = built_bundle
        x = jnp.asarray(RNG.standard_normal((128, 128)), jnp.float32)
        y = jnp.asarray(RNG.standard_normal((128, 128)), jnp.float32)
        q = jnp.asarray(RNG.standard_normal((1, 1, 128, 64)), jnp.float32)
        # baseline: same blocks via explicit blocks=, compiled the slow way
        base_mm = np.asarray(ops.matmul(x, y, blocks=(64, 64, 64),
                                        target="tpu_v5e"))
        base_att = np.asarray(ops.attention(q, q, q, blocks=(64, 64),
                                            target="tpu_v5e"))
        ops.use_kernel_bundle(binfo.path)
        ops.reset_pallas_trace_counts()
        got_mm = np.asarray(ops.matmul(x, y, target="tpu_v5e"))
        got_att = np.asarray(ops.attention(q, q, q, target="tpu_v5e"))
        counts = ops.pallas_trace_counts()
        assert counts == {"matmul": 0, "flash": 0}  # the AOT witness
        assert ops.get_kernel_bundle().exec_hits == 2
        # identical block configs -> bitwise-identical outputs
        np.testing.assert_array_equal(got_mm, base_mm)
        np.testing.assert_array_equal(got_att, base_att)

    def test_without_bundle_first_call_traces(self):
        x = jnp.ones((128, 128), jnp.float32)
        ops.reset_pallas_trace_counts()
        ops.matmul(x, x, target="tpu_v5e")
        assert ops.pallas_trace_counts()["matmul"] == 1

    def test_tracer_args_fall_through_to_trace_path(self, built_bundle):
        """Under an outer jit the args are tracers — the AOT executable
        cannot serve them, and the call must still work."""
        _, _, binfo = built_bundle
        ops.use_kernel_bundle(binfo.path)
        ops.reset_pallas_trace_counts()
        x = jnp.asarray(RNG.standard_normal((128, 128)), jnp.float32)

        @jax.jit
        def f(a, b):
            return ops.matmul(a, b, target="tpu_v5e")

        np.testing.assert_allclose(np.asarray(f(x, x)),
                                   np.asarray(x) @ np.asarray(x),
                                   rtol=1e-5, atol=1e-4)
        assert ops.pallas_trace_counts()["matmul"] == 1  # traced normally

    def test_bundle_is_first_schedule_tier(self, built_bundle):
        _, _, binfo = built_bundle
        ops.use_kernel_bundle(binfo.path)
        assert ops.tuned_flash_blocks(128, 64, 4, "tpu_v5e") == (64, 64)
        bundle = ops.get_kernel_bundle()
        assert bundle.hits >= 1
        rec, source = tuner._lookup(MM_OP, TGT, rec_version(), None)
        assert source == "bundle" and rec.score == 1e-6

    def test_env_var_fallback_and_stale_degrade(self, built_bundle,
                                                tmp_path, monkeypatch):
        _, _, binfo = built_bundle
        monkeypatch.setenv("REPRO_TUNA_BUNDLE", binfo.path)
        monkeypatch.setattr(tuner, "_DEFAULT_BUNDLE", tuner._UNSET)
        assert tuner.get_default_bundle() is not None
        # a stale bundle degrades to OFF loudly and clears the memos
        obj = json.load(open(binfo.path))
        obj["cost_model_version"] = "cm0"
        stale = str(tmp_path / "stale_bundle.json")
        json.dump(obj, open(stale, "w"))
        cleared = []
        tuner.register_memo_clearer(lambda: cleared.append(1))
        try:
            monkeypatch.setenv("REPRO_TUNA_BUNDLE", stale)
            monkeypatch.setattr(tuner, "_DEFAULT_BUNDLE", tuner._UNSET)
            with pytest.warns(StaleSnapshotWarning,
                              match="REPRO_TUNA_BUNDLE disabled"):
                assert tuner.get_default_bundle() is None
            assert cleared
        finally:
            tuner._MEMO_CLEARERS.pop()


def rec_version():
    from repro.core.cost_model import COST_MODEL_VERSION

    return COST_MODEL_VERSION


class TestStaleCacheDegradeClearsMemos:
    def test_env_cache_stale_degrade_clears_memos(self, tmp_path,
                                                  monkeypatch):
        """Regression (the PR's satellite bug): $REPRO_TUNA_CACHE degrading
        to OFF used to leave the block-spec memos warm, so shapes memoised
        under an earlier snapshot kept serving its blocks after the
        snapshot was rejected."""
        db = ScheduleDatabase(tmp_path / "db.jsonl")
        db.add(ScheduleRecord(
            op="flash[d=128,dtype_bytes=2,s=2048]", target=TGT,
            config={"block_q": 256, "block_k": 128}, score=1e-9))
        snap = str(tmp_path / "cache.json")
        ScheduleCache.build(db.path, snap)
        tuner.set_default_cache(snap)
        assert ops.tuned_flash_blocks(2048, 128, 2, "tpu_v5e") == (256, 128)  # memoised

        obj = json.load(open(snap))
        obj["cost_model_version"] = "cm0"
        stale = str(tmp_path / "stale.json")
        json.dump(obj, open(stale, "w"))
        monkeypatch.setenv("REPRO_TUNA_CACHE", stale)
        monkeypatch.setattr(tuner, "_DEFAULT_CACHE", tuner._UNSET)
        with pytest.warns(StaleSnapshotWarning,
                          match="REPRO_TUNA_CACHE disabled"):
            assert tuner.get_default_cache() is None
        # memo must have been dropped with the cache: the pick re-resolves
        # to the heuristic, not the rejected snapshot's record
        assert ops.tuned_flash_blocks(2048, 128, 2, "tpu_v5e") != (256, 128)


class TestPublishRoundtrip:
    def test_golden_and_bundle_ship_over_mem_transport(self, tmp_path):
        src = tmp_path / "src"
        dst = tmp_path / "dst"
        os.makedirs(dst)
        mgr = GoldenManager(str(src))
        info = mgr.promote(mk_records(), TGT)
        _, release = mgr.load_release(info.path)
        binfo = build_kernel_bundle(release, str(src), TGT,
                                    golden_name=info.name)
        t = _mem(tmp_path)
        manifests = mgr.publish(t, info, bundle=binfo)
        assert len(manifests) == 4  # release + pointer, bundle + pointer
        for name in t.list():
            t.pull(name, str(dst / name))
        # the pulled pointer resolves inside the destination directory
        hdr, records = GoldenManager(str(dst)).load_release(
            str(dst / os.path.basename(info.latest)))
        assert hdr["sha1"] == info.sha1 and len(records) == 3
        bundle = KernelBundle.load(str(dst / os.path.basename(binfo.latest)))
        assert bundle.sha1 == binfo.sha1 and len(bundle) == 2
        x = jnp.ones((128, 128), jnp.float32)
        assert bundle.executable("matmul", (x, x)) is not None


class TestServeParity:
    def test_serve_with_bundle_token_identical(self, tmp_path):
        """Acceptance: a bundled serve produces the exact greedy tokens of
        an unbundled serve (cold start skips compiles, never changes
        outputs)."""
        from repro.configs.base import get_config
        from repro.launch.engine import Request
        from repro.launch.serve import serve
        from repro.models.model import Model

        mgr = GoldenManager(str(tmp_path))
        info = mgr.promote(mk_records(), TGT)
        _, release = mgr.load_release(info.path)
        binfo = build_kernel_bundle(release, str(tmp_path), TGT,
                                    golden_name=info.name)

        cfg = get_config("yi_6b").reduced()
        model = Model(cfg)
        params = model.init(jax.random.key(0))
        rng = np.random.default_rng(11)
        prompts = [list(rng.integers(0, cfg.vocab, 4)) for _ in range(3)]

        def run():
            reqs = [Request(i, list(p), 4) for i, p in enumerate(prompts)]
            serve(model, params, reqs, slots=2, cap=12)
            return [r.out for r in reqs]

        plain = run()
        ops.use_kernel_bundle(binfo.path)
        bundled = run()
        assert bundled == plain


class TestGoldenCLI:
    def _write_db(self, path, records):
        db = ScheduleDatabase(path)
        for r in records:
            db.add(r)
        return str(path)

    def test_cli_end_to_end_with_bundle(self, tmp_path, capsys):
        db = self._write_db(tmp_path / "db.jsonl", mk_records())
        gdir = str(tmp_path / "golden")
        assert cli.main(["golden", "--db", db, "--dir", gdir,
                         "--bundle"]) == 0
        out = capsys.readouterr().out
        assert "promoted" in out and "first release in this lineage" in out
        assert "2 AOT kernel(s) over 3 schedules" in out
        assert "no AOT kernel for conv2d[foo=1]" in out
        # re-run: content-addressed no-op, still gated against itself
        assert cli.main(["golden", "--db", db, "--dir", gdir]) == 0
        out = capsys.readouterr().out
        assert "up to date" in out and "gated against" in out
        names = os.listdir(gdir)
        assert any(n.startswith(f"golden.{TGT}.") and "latest" not in n
                   for n in names)
        assert any(n.startswith(f"bundle.{TGT}.") and "latest" not in n
                   for n in names)

    def test_cli_refuses_regression_then_waives(self, tmp_path, capsys):
        gdir = str(tmp_path / "golden")
        good = self._write_db(tmp_path / "good.jsonl", mk_records())
        assert cli.main(["golden", "--db", good, "--dir", gdir]) == 0
        capsys.readouterr()
        worse = self._write_db(tmp_path / "worse.jsonl",
                               mk_records(mm_score=5e-6))
        assert cli.main(["golden", "--db", worse, "--dir", gdir]) == 1
        err = capsys.readouterr().err
        assert "REFUSED golden promotion" in err and MM_OP in err
        assert cli.main(["golden", "--db", worse, "--dir", gdir,
                         "--waive", f"{MM_OP}@{TGT}"]) == 0
        err = capsys.readouterr().err
        assert "WAIVED" in err

    def test_cli_publish_over_mem(self, tmp_path, capsys):
        db = self._write_db(tmp_path / "db.jsonl", mk_records())
        t = _mem(tmp_path)
        url = f"mem://{t.bucket}"
        assert cli.main(["golden", "--db", db,
                         "--dir", str(tmp_path / "g"),
                         "--publish", url]) == 0
        assert "published" in capsys.readouterr().out
        assert any(n.startswith("golden.") for n in t.list())

    def test_cli_no_records_is_an_error(self, tmp_path, capsys):
        db = str(tmp_path / "empty.jsonl")
        ScheduleDatabase(db)
        assert cli.main(["golden", "--db", db,
                         "--dir", str(tmp_path / "g")]) == 2
        assert "no records" in capsys.readouterr().err


class TestCompactExportGuards:
    def _base_with_shards(self, tmp_path):
        from repro.tuna.fleet import shard_store_path

        base = str(tmp_path / "db.jsonl")
        db = ScheduleDatabase(base)
        db.add(mk_records()[0])
        shard = ScheduleDatabase(shard_store_path(base, 0))
        shard.add(mk_records(fl_score=7e-7)[1])
        return base, shard.path

    def test_compact_refuses_stale_partial_store(self, tmp_path, capsys):
        """Regression (the PR's satellite bug): compact used to silently
        rewrite the base store while fleet shards sat next to it."""
        base, _ = self._base_with_shards(tmp_path)
        assert cli.main(["compact", "--db", base]) == 2
        err = capsys.readouterr().err
        assert "per-shard store" in err and "sync" in err
        assert cli.main(["compact", "--db", base, "--ignore-shards"]) == 0

    def test_export_refuses_stale_partial_store(self, tmp_path, capsys):
        base, _ = self._base_with_shards(tmp_path)
        out = str(tmp_path / "best.json")
        assert cli.main(["export", "--db", base, "--out", out]) == 2
        assert not os.path.exists(out)
        assert cli.main(["export", "--db", base, "--out", out,
                         "--ignore-shards"]) == 0
        assert len(json.load(open(out))) == 1  # base store only, by choice

    def test_compact_with_transport_pulls_merges_pushes(self, tmp_path,
                                                        capsys):
        from repro.tuna.fleet import shard_store_path

        t = _mem(tmp_path)
        url = f"mem://{t.bucket}"
        # the fleet published two shard stores on the channel
        pub = tmp_path / "pub"
        os.makedirs(pub)
        for i, rec in enumerate(mk_records(with_conv=False)):
            p = shard_store_path(str(pub / "db.jsonl"), i)
            ScheduleDatabase(p).add(rec)
            t.push(p, os.path.basename(p))
        work = tmp_path / "work"
        os.makedirs(work)
        base = str(work / "db.jsonl")
        assert cli.main(["compact", "--db", base, "--transport", url,
                         "--num-shards", "2"]) == 0
        out = capsys.readouterr().out
        assert out.count("pulled") == 2 and "compacted" in out
        assert len(ScheduleDatabase(base)) == 2  # both shards absorbed
        # and the merged store went back on the channel under its base name
        assert "db.jsonl" in t.list()

    def test_transport_without_num_shards_fails_fast(self, tmp_path,
                                                     capsys):
        t = _mem(tmp_path)
        rc = cli.main(["export", "--db", str(tmp_path / "db.jsonl"),
                       "--out", str(tmp_path / "o.json"),
                       "--transport", f"mem://{t.bucket}"])
        assert rc == 2
        assert "--num-shards" in capsys.readouterr().err

    def test_export_with_transport_covers_the_fleet(self, tmp_path, capsys):
        from repro.tuna.fleet import shard_store_path

        t = _mem(tmp_path)
        pub = tmp_path / "pub"
        os.makedirs(pub)
        p = shard_store_path(str(pub / "db.jsonl"), 0)
        ScheduleDatabase(p).add(mk_records()[0])
        t.push(p, os.path.basename(p))
        work = tmp_path / "work"
        os.makedirs(work)
        out = str(work / "best.json")
        assert cli.main(["export", "--db", str(work / "db.jsonl"),
                         "--out", out, "--transport", f"mem://{t.bucket}",
                         "--num-shards", "2"]) == 0
        err = capsys.readouterr().err
        assert "not published yet" in err  # shard 1 missing -> loud warning
        assert len(json.load(open(out))) == 1


class TestColdStartBench:
    def test_check_gates(self):
        from benchmarks.cold_start import check

        good = {"cold_start": {
            "unbundled": {"wall_s": 0.2,
                          "pallas_traces": {"matmul": 1, "flash": 1}},
            "bundled": {"wall_s": 0.01,
                        "pallas_traces": {"matmul": 0, "flash": 0}},
            "parity": {"ok": True, "max_abs_diff": 0.0},
        }}
        assert check(good) == []
        import copy

        slow = copy.deepcopy(good)
        slow["cold_start"]["bundled"]["wall_s"] = 0.3
        assert any("strictly faster" in m for m in check(slow))
        traced = copy.deepcopy(good)
        traced["cold_start"]["bundled"]["pallas_traces"]["matmul"] = 1
        assert any("traced Pallas" in m for m in check(traced))
        diverged = copy.deepcopy(good)
        diverged["cold_start"]["parity"] = {"ok": False,
                                            "max_abs_diff": 1.0}
        assert any("diverge" in m for m in check(diverged))
        unmeasured = copy.deepcopy(good)
        unmeasured["cold_start"]["unbundled"]["pallas_traces"] = {
            "matmul": 0, "flash": 0}
        assert any("not measuring" in m for m in check(unmeasured))

    @pytest.mark.slow
    def test_full_benchmark_passes_its_own_check(self, tmp_path):
        from benchmarks.cold_start import check, run_benchmark

        result = run_benchmark(iters=1, ct_configs=4,
                               workdir=str(tmp_path))
        assert check(result) == []
        assert result["cold_start"]["speedup"] > 1.0
