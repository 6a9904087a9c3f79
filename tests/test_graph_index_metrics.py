"""Tests for GraphTable (reference distributed/test/graph_node_test.cc
patterns), TreeIndex/LayerWiseSampler (unittests/test_index_dataset.py),
basic metrics (metrics.h BasicAucCalculator variants), and the profiler
chrome-tracing export."""

import json
import os

import numpy as np
import pytest

from paddle_tpu.core.profiler import (
    RecordEvent,
    export_chrome_tracing,
    start_timeline,
)
from paddle_tpu.data import LayerWiseSampler, TreeIndex
from paddle_tpu.metrics import MAE, RMSE, WuAUC
from paddle_tpu.ps import GraphTable


class TestGraphTable:
    def _toy(self):
        g = GraphTable(shard_num=4, seed=0)
        g.add_graph_node([0, 1, 2, 3, 4])
        g.add_edges([0, 0, 0, 1, 2], [1, 2, 3, 2, 3], [1.0, 2.0, 3.0, 1.0, 1.0])
        return g

    def test_counts_and_degree(self):
        g = self._toy()
        assert g.node_count == 5
        assert g.edge_count == 5
        np.testing.assert_array_equal(g.get_node_degree([0, 1, 2, 3, 4]),
                                      [3, 1, 1, 0, 0])

    def test_sample_neighbors_padded(self):
        g = self._toy()
        nbrs, mask = g.sample_neighbors([0, 3, 1], sample_size=4)
        assert nbrs.shape == (3, 4) and mask.shape == (3, 4)
        assert mask[0].sum() == 3          # node 0 has 3 neighbors
        assert set(nbrs[0][mask[0]]) == {1, 2, 3}
        assert mask[1].sum() == 0          # node 3 has none
        assert mask[2].sum() == 1 and nbrs[2, 0] == 2

    def test_weighted_sampling_bias(self):
        g = GraphTable(shard_num=2, seed=1)
        g.add_edges([0] * 2, [1, 2], [100.0, 1.0])
        hits = 0
        for _ in range(50):
            nbrs, mask = g.sample_neighbors([0], sample_size=1)
            if nbrs[0, 0] == 1:
                hits += 1
        assert hits > 40  # heavy-weight neighbor dominates

    def test_features(self):
        g = GraphTable(shard_num=2)
        g.add_graph_node([7, 8], np.asarray([[1, 2], [3, 4]], np.float32))
        feats = g.get_node_feat([7, 8, 99], feat_dim=2)
        np.testing.assert_allclose(feats[:2], [[1, 2], [3, 4]])
        np.testing.assert_allclose(feats[2], 0)
        g.set_node_feat([7], np.asarray([[9, 9]], np.float32))
        np.testing.assert_allclose(g.get_node_feat([7], 2), [[9, 9]])
        with pytest.raises(Exception):
            g.set_node_feat([12345], np.zeros((1, 2), np.float32))

    def test_load_files(self, tmp_path):
        ef = tmp_path / "edges.txt"
        ef.write_text("0\t1\t2.0\n1\t2\n")
        nf = tmp_path / "nodes.txt"
        nf.write_text("0\t0.5\t0.5\n1\n2\n")
        g = GraphTable(shard_num=2)
        assert g.load_edges(str(ef)) == 2
        assert g.load_nodes(str(nf)) == 3
        assert g.node_count == 3
        np.testing.assert_allclose(g.get_node_feat([0], 2), [[0.5, 0.5]])

    def test_zero_weight_edges_sampled_safely(self):
        g = GraphTable(shard_num=2, seed=0)
        g.add_edges([0, 0, 0], [1, 2, 3], [1.0, 0.0, 0.0])
        nbrs, mask = g.sample_neighbors([0], sample_size=3)
        # only the positive-weight neighbor is samplable
        assert mask[0].sum() == 1 and nbrs[0, 0] == 1

    def test_sample_nodes(self):
        g = self._toy()
        s = g.sample_nodes(10)
        assert len(s) == 10
        assert set(s).issubset({0, 1, 2, 3, 4})


class TestTreeIndex:
    def test_structure(self):
        t = TreeIndex(list(range(100, 108)), branch=2)
        assert t.height == 3
        assert len(t.get_layer_codes(0)) == 1
        assert len(t.get_layer_codes(1)) == 2
        assert len(t.get_layer_codes(3)) == 8

    def test_travel_path(self):
        t = TreeIndex(list(range(100, 108)), branch=2)
        path = t.get_travel_codes(100)  # first leaf
        assert path[-1] == 0            # ends at root
        assert len(path) == t.height + 1
        # each step is the parent of the previous
        for a, b in zip(path, path[1:]):
            assert (a - 1) // 2 == b

    def test_items_of_codes(self):
        t = TreeIndex([5, 6, 7], branch=2)
        leaf = t.get_travel_codes(6)[0]
        assert t.get_items_of_codes([leaf]) == [6]
        assert t.get_items_of_codes([0]) == [None]

    def test_missing_item(self):
        t = TreeIndex([1, 2], branch=2)
        with pytest.raises(Exception):
            t.get_travel_codes(999)

    def test_layerwise_sampler(self):
        t = TreeIndex(list(range(16)), branch=2)  # height 4
        sampler = LayerWiseSampler(t, layer_counts=[1, 2, 2, 3], seed=0)
        idx, codes, labels = sampler.sample([3, 9])
        assert len(idx) == len(codes) == len(labels)
        # positives: one per layer per item
        assert labels.sum() == 2 * 4
        # negatives never equal the positive of their layer
        for pi in (0, 1):
            sel = idx == pi
            pos_codes = set(codes[sel][labels[sel] == 1].tolist())
            neg_codes = set(codes[sel][labels[sel] == 0].tolist())
            assert not pos_codes & neg_codes


class TestBasicMetrics:
    def test_mae_rmse(self):
        mae, rmse = MAE(), RMSE()
        preds = np.asarray([1.0, 2.0, 3.0])
        labels = np.asarray([1.5, 2.0, 5.0])
        mae.update(preds, labels)
        rmse.update(preds, labels)
        np.testing.assert_allclose(mae.accumulate(), (0.5 + 0 + 2) / 3)
        np.testing.assert_allclose(rmse.accumulate(),
                                   np.sqrt((0.25 + 0 + 4) / 3))

    def test_mask(self):
        mae = MAE()
        mae.update([1.0, 100.0], [0.0, 0.0], mask=[1, 0])
        np.testing.assert_allclose(mae.accumulate(), 1.0)

    def test_merge_across_workers(self):
        a, b = MAE(), MAE()
        a.update([1.0], [0.0])
        b.update([3.0], [0.0])
        a.merge(b.state)
        np.testing.assert_allclose(a.accumulate(), 2.0)

    def test_wuauc_perfect_and_random(self):
        m = WuAUC()
        # user 1: perfectly ranked; user 2: inverted
        m.update([1, 1, 1, 1], [0.1, 0.2, 0.8, 0.9], [0, 0, 1, 1])
        m.update([2, 2], [0.9, 0.1], [0, 1])
        # user1 auc=1 (w=4), user2 auc=0 (w=2) → 4/6
        np.testing.assert_allclose(m.accumulate(), 4 / 6)

    def test_wuauc_single_class_user_skipped(self):
        m = WuAUC()
        m.update([1, 1], [0.5, 0.6], [1, 1])     # no negatives: skipped
        m.update([2, 2], [0.2, 0.9], [0, 1])     # auc 1
        np.testing.assert_allclose(m.accumulate(), 1.0)

    def test_wuauc_merge(self):
        a, b = WuAUC(), WuAUC()
        a.update([1, 1], [0.2, 0.9], [0, 1])
        b.update([1, 1], [0.3, 0.8], [0, 1])
        a.merge(b.state)
        assert a.accumulate() == 1.0

    def test_wuauc_ties_average(self):
        m = WuAUC()
        # all predictions tied: AUC must be exactly 0.5
        m.update([1] * 6, [0.5] * 6, [0, 1, 0, 1, 0, 1])
        np.testing.assert_allclose(m.accumulate(), 0.5)

    def test_wuauc_large_user_fast(self):
        import time as _t

        rng = np.random.default_rng(0)
        n = 200_000
        m = WuAUC()
        m.update(np.ones(n), rng.random(n), rng.integers(0, 2, n))
        t0 = _t.monotonic()
        v = m.accumulate()
        assert _t.monotonic() - t0 < 5.0  # O(n log n), not O(n^2)
        assert 0.45 < v < 0.55


class TestChromeTracing:
    def test_export(self, tmp_path):
        start_timeline()
        with RecordEvent("phase_a"):
            with RecordEvent("phase_b"):
                pass
        out = export_chrome_tracing(str(tmp_path / "trace.json"))
        blob = json.load(open(out))
        names = [e["name"] for e in blob["traceEvents"]]
        assert "phase_a" in names and "phase_b" in names
        for e in blob["traceEvents"]:
            assert e["ph"] == "X" and e["dur"] >= 0
