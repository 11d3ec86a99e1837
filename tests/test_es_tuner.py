"""Evolution Strategies (Alg. 4) + tuner end-to-end."""
import numpy as np
import pytest

from repro.core import MatmulSpace, evolve, rank_space, tune
from repro.core.tuner import tuned_matmul_blocks
from repro.hw import get_target

TPU = get_target("tpu_v5e")


class TestES:
    def test_optimizes_quadratic(self):
        target = np.array([1.5, -2.0, 0.5])

        def fitness(theta):
            return -float(np.sum((theta - target) ** 2))

        res = evolve(fitness, dim=3, iterations=40, population=24, seed=0)
        assert res.best_fitness > -0.5
        assert np.allclose(res.best_theta, target, atol=1.0)

    def test_deterministic_given_seed(self):
        def fitness(theta):
            return -float(np.sum(theta ** 2))

        a = evolve(fitness, dim=4, iterations=5, population=8, seed=7)
        b = evolve(fitness, dim=4, iterations=5, population=8, seed=7)
        assert np.allclose(a.best_theta, b.best_theta)
        assert a.best_fitness == b.best_fitness

    def test_history_monotone(self):
        def fitness(theta):
            return -float(np.sum(theta ** 2))

        res = evolve(fitness, dim=2, iterations=10, population=8, seed=1)
        assert all(a <= b + 1e-12 for a, b in zip(res.history, res.history[1:]))


class TestTuner:
    def test_es_matches_exhaustive_on_small_space(self):
        space = MatmulSpace(1024, 1024, 1024, 2, target_kind="tpu")
        exhaustive = rank_space(space, TPU, limit=1024)
        res = tune(space, TPU, iterations=12, population=16, seed=0)
        best_exhaustive = exhaustive[0][1]
        # ES should land within 25% of the global optimum's score
        assert res.score <= best_exhaustive * 1.25
        assert res.score <= res.default_score  # never worse than default

    def test_vmem_constraint_respected(self):
        space = MatmulSpace(4096, 4096, 4096, 2, target_kind="tpu")
        ranked = rank_space(space, TPU, limit=1024)
        cfg = ranked[0][0]
        tile = (cfg["bm"] * cfg["bk"] + cfg["bk"] * cfg["bn"]
                + cfg["bm"] * cfg["bn"]) * 2
        bufs = 2 if cfg["double_buffer"] else 1
        assert tile * bufs <= TPU.fast_mem_bytes

    def test_tuned_blocks_divide_shape(self):
        bm, bn, bk = tuned_matmul_blocks(2048, 2048, 2048, 2, "tpu_v5e")
        assert 2048 % bm == 0 and 2048 % bn == 0 and 2048 % bk == 0
        # hardware-aligned tiles
        assert bn % 128 == 0 and bk % 128 == 0

    def test_ranking_penalises_misaligned_tiles(self):
        """8-wide M tiles waste 15/16 of the MXU; the model must rank a
        128-aligned tile above them."""
        space = MatmulSpace(2048, 2048, 2048, 2, target_kind="tpu")
        ranked = rank_space(space, TPU, limit=1024)
        best = ranked[0][0]
        assert best["bm"] >= 128
