"""Native C++ core: build, parity with the Python fallbacks, integration
through NetworkIndex (reference models: structs/network_test.go port
assignment tests; structs_test.go AllocsFit/ScoreFit tests)."""
import numpy as np
import pytest

from nomad_tpu import native


@pytest.fixture(scope="module", autouse=True)
def native_built():
    assert native.available(), (
        "g++ is present in this image — the native core must build")


def _rand_used(rng, frac):
    used = np.zeros(65536, dtype=bool)
    n = int(65536 * frac)
    used[rng.choice(65536, size=n, replace=False)] = True
    return used


class TestFirstFitPorts:
    def test_matches_python_fallback(self):
        rng = np.random.default_rng(7)
        for frac in (0.0, 0.3, 0.9):
            used = _rand_used(rng, frac)
            reserved = [20000, 20001, 25000]
            got = native.first_fit_ports(used, 20000, 32000, reserved, 5)
            want = native._first_fit_py(used, 20000, 32000, reserved, 5)
            assert got == want

    def test_exhaustion_returns_empty(self):
        used = np.ones(65536, dtype=bool)
        assert native.first_fit_ports(used, 20000, 32000, [], 1) == []

    def test_skips_reserved(self):
        used = np.zeros(65536, dtype=bool)
        got = native.first_fit_ports(used, 20000, 32000, [20000, 20002], 3)
        assert got == [20001, 20003, 20004]

    def test_zero_count(self):
        used = np.zeros(65536, dtype=bool)
        assert native.first_fit_ports(used, 20000, 32000, [], 0) == []


class TestFitsAndScore:
    def test_fits_batch_parity(self):
        rng = np.random.default_rng(3)
        N, R = 64, 8
        capacity = rng.uniform(100, 4000, (N, R)).astype(np.float32)
        used = (capacity * rng.uniform(0, 1.2, (N, R))).astype(np.float32)
        ask = rng.uniform(0, 500, R).astype(np.float32)
        rows = np.arange(N, dtype=np.int32)
        got = native.fits_batch(capacity, used, ask, rows)
        want = np.all(capacity - used >= ask[None, :], axis=1)
        np.testing.assert_array_equal(got, want)

    def test_score_binpack_parity_with_reference_formula(self):
        capacity = np.array([[4000, 8192, 0, 0]], dtype=np.float32)
        used = np.array([[1000, 2048, 0, 0]], dtype=np.float32)
        ask = np.array([500, 1024, 0, 0], dtype=np.float32)
        rows = np.array([0], dtype=np.int32)
        got = float(native.score_binpack(capacity, used, ask, rows)[0])
        free_cpu = (4000 - 1000 - 500) / 4000
        free_mem = (8192 - 2048 - 1024) / 8192
        want = 20.0 - 10 ** free_cpu - 10 ** free_mem
        assert abs(got - want) < 1e-4

    def test_score_matches_structs_funcs(self):
        """Native score == the framework's parity-anchor scorer
        (capacity rows = resources − reserved, funcs.go:150)."""
        from nomad_tpu import mock
        from nomad_tpu.structs.funcs import score_fit_binpack
        from nomad_tpu.structs.resources import ComparableResources

        node = mock.node()
        util = ComparableResources(cpu=1500.0, memory_mb=3072.0)
        want = score_fit_binpack(node, util)
        res = node.comparable_resources()
        reserved = node.comparable_reserved_resources()
        cap = np.array([[res.cpu - reserved.cpu,
                         res.memory_mb - reserved.memory_mb]],
                       dtype=np.float32)
        used = np.array([[1500.0, 3072.0]], dtype=np.float32)
        got = float(native.score_binpack(
            cap, used, np.zeros(2, dtype=np.float32),
            np.array([0], dtype=np.int32))[0])
        assert abs(got - want) < 1e-3

    def test_scatter_add_roundtrip(self):
        used = np.zeros((8, 4), dtype=np.float32)
        rows = np.array([1, 3, 1], dtype=np.int32)
        usage = np.arange(12, dtype=np.float32).reshape(3, 4)
        native.scatter_add(used, rows, usage, 1.0)
        want = np.zeros((8, 4), dtype=np.float32)
        np.add.at(want, rows, usage)
        np.testing.assert_allclose(used, want)
        native.scatter_add(used, rows, usage, -1.0)
        np.testing.assert_allclose(used, np.zeros((8, 4)))

    def test_count_free_ports(self):
        used = np.zeros(65536, dtype=bool)
        used[20000:20010] = True
        assert native.count_free_ports(used, 20000, 20020) == 10


class TestNetworkIndexIntegration:
    def test_assign_network_uses_native_path(self):
        from nomad_tpu import mock
        from nomad_tpu.structs.network import NetworkIndex
        from nomad_tpu.structs.resources import NetworkResource, Port

        node = mock.node()
        idx = NetworkIndex()
        idx.set_node(node)
        ask = NetworkResource(mbits=10, dynamic_ports=[
            Port(label="http"), Port(label="metrics")])
        offer, err = idx.assign_network(ask)
        assert err == "" and offer is not None
        vals = [p.value for p in offer.dynamic_ports]
        assert len(set(vals)) == 2
        assert all(20000 <= v < 32000 for v in vals)


class TestCompiledSelect:
    """The C++ select loop (nomad_select_eval) must agree with the TPU
    kernel / Python oracle on node choice and normalized score — it is the
    compiled reference of `scheduler/parity.py` (the chip smoke's
    `compiled_parity` check) and must not decide by a different algorithm."""

    @pytest.mark.skipif(not native.available(), reason="no native lib")
    def test_agrees_with_kernel(self):
        import random

        from nomad_tpu.scheduler.stack import TPUStack
        from nomad_tpu.synth import build_synthetic_state, synth_service_job

        state, nodes = build_synthetic_state(64, 100, seed=3)
        rng = random.Random(5)
        cl = state.cluster
        from nomad_tpu.structs import Spread

        for i, variant in enumerate([
            dict(),
            dict(with_affinity=True),
            dict(with_spread=True),
            dict(distinct_hosts=True),
            dict(with_affinity=True, with_spread=True, distinct_hosts=True),
            dict(distinct_property=True),
            "even_spread",
        ]):
            if variant == "even_spread":
                job = synth_service_job(rng, count=4)
                job.spreads.append(Spread(attribute="${node.datacenter}",
                                          weight=100))
            else:
                job = synth_service_job(rng, count=4, **variant)
            tg = job.task_groups[0]
            stack = TPUStack(cl)
            sel_k = stack.select(job, tg, 4)
            out = native.compiled_select(stack, job, tg, 4)
            assert out is not None
            sel_c, score_c = out
            for step in range(4):
                k_node = sel_k.node_ids[step]
                c_node = (cl.node_of_row[sel_c[step]]
                          if sel_c[step] >= 0 else None)
                if k_node is None or c_node is None:
                    assert k_node is None and c_node is None, (i, step)
                    continue
                assert abs(sel_k.scores[step] - score_c[step]) < 1e-4, (
                    i, step, k_node, c_node,
                    sel_k.scores[step], score_c[step])


class TestCompiledSelectSampled:
    """The reference's ACTUAL select shape (scheduler/stack.go:10-18 +
    LimitIterator): log2(n) candidates from a shuffled walk, maxSkip 3.
    Placement quality may trail the exact scan; validity must not."""

    def _problem(self, n_nodes=512, seed=5):
        import random

        from nomad_tpu.scheduler.stack import TPUStack
        from nomad_tpu.synth import build_synthetic_state, synth_service_job

        state, _ = build_synthetic_state(n_nodes, n_nodes // 2, seed=seed)
        rng = random.Random(seed + 1)
        job = synth_service_job(rng, count=8, with_affinity=True)
        state.upsert_job(job)
        return state.cluster, TPUStack(state.cluster), job

    def test_sampled_places_validly(self):
        import numpy as np

        cl, stack, job = self._problem()
        tg = job.task_groups[0]
        rng = np.random.default_rng(3)
        order = rng.permutation(cl.n_cap).astype(np.int32)
        out = native.compiled_select(stack, job, tg, 8, order=order)
        assert out is not None
        sel, score = out
        assert (sel >= 0).all()  # everything placed
        # every selected row is a real, eligible node
        for row in sel:
            assert cl.node_ok[row]
        # scores are the same normalized scale the exact loop emits
        assert (score > 0).all() and (score <= 1.5).all()

    def test_sampled_quality_trails_exact_boundedly(self):
        """The throughput win of sampling is bought with placement
        quality: exact mean score >= sampled mean score, and both loops
        place everything. (This is the delta BASELINE.md reports.)"""
        import numpy as np

        cl, stack, job = self._problem()
        tg = job.task_groups[0]
        exact = native.compiled_select(stack, job, tg, 8)
        rng = np.random.default_rng(4)
        order = rng.permutation(cl.n_cap).astype(np.int32)
        sampled = native.compiled_select(stack, job, tg, 8, order=order)
        assert exact is not None and sampled is not None
        mean_exact = float(exact[1].mean())
        mean_sampled = float(sampled[1].mean())
        assert (sampled[0] >= 0).all()
        assert mean_exact >= mean_sampled - 1e-6, (
            mean_exact, mean_sampled)

    def test_limit_window_is_log2(self):
        """With a single feasible node hidden at the end of the order and
        limit defaulting to ceil(log2(n)), the sampled walk must still
        find it — infeasible nodes do not consume the window."""
        import numpy as np

        cl, stack, job = self._problem(n_nodes=64)
        tg = job.task_groups[0]
        # shuffled order that puts every row in play; feasibility of most
        # rows is irrelevant to the window since infeasible rows are free
        order = np.arange(cl.n_cap, dtype=np.int32)[::-1].copy()
        out = native.compiled_select(stack, job, tg, 4, order=order,
                                     max_skip=0)
        assert out is not None and (out[0] >= 0).all()
