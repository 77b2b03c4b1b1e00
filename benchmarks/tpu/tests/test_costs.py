"""costs/gat_e.py and costs/gcn.py against counts made by hand on a tiny
view: 3 source nodes, 2 computed nodes, 4 live edges, one layer."""
import pytest

import bench

gat_e = bench.load_module(bench.HERE / "costs" / "gat_e.py")
gcn = bench.load_module(bench.HERE / "costs" / "gcn.py")

WORK = {"layers": [{"n_src": 3, "n_dst": 2, "edges": 4}], "targets": 2}


def test_gat_e_forward_by_hand():
    # 1 layer, in 5 -> 2 heads x 3, 2 edge attributes, 2 classes
    model = {"num_heads": 2, "hidden_dim": 6, "edge_feature_dim": 2,
             "num_classes": 2, "num_layers": 1}
    proj = 2 * 3 * 5 * 6                 # h W over 3 source rows
    att = 2 * 3 * 6 + 2 * 2 * 6          # a_src . n (sources), a_dst . n
    edge = 2 * 4 * 2 * 2 + 2 * 4 * 2 * 6  # x_e W_ea, x_e W_ev
    logit_value = 3 * 4 * 2 + 4 * 6
    softmax = 4 * 2 * (3 + 2 * 3) + 2 * 6
    apply = 2 * 2 * 6
    decoder = 2 * 2 * 6 * 2
    want = proj + att + edge + logit_value + softmax + apply + decoder
    assert gat_e.forward_flops(WORK, model, 5) == want
    assert gat_e.train_flops(WORK, model, 5) == 3 * want


def test_gat_e_sum_stage_bytes_by_hand():
    model = {"num_heads": 2, "hidden_dim": 6, "edge_feature_dim": 2,
             "num_classes": 2, "num_layers": 1}
    (ffl, fby), (bfl, bby) = gat_e.sum_stage_passes(WORK, model, 5, True)
    # forward: logits 4x2, values 4x6, 4 indices, output 2x6 (float32)
    assert fby == 4 * (8 + 24 + 4 + 12)
    # backward: grad in and output 2x(2x6), stats 2x(2x2), logits and
    # their grads 2x(4x2), values and their grads 2x(4x6), 4 indices
    assert bby == 4 * (24 + 8 + 16 + 48 + 4)
    assert bfl == 2 * ffl == 2 * (4 * 2 * 9 + 12)


def test_gcn_by_hand():
    model = {"hidden_dim": 4, "num_classes": 3, "num_layers": 1}
    want = (2 * 3 * 5 * 4) + 4 * 4 + 4 * 4 + 2 * 2 * 4 + 2 * 2 * 4 * 3
    assert gcn.forward_flops(WORK, model, 5) == want
    (ffl, fby), (bfl, bby) = gcn.sum_stage_passes(WORK, model, 5, True)
    assert (ffl, fby) == (16, 4 * (16 + 4 + 8))
    assert (bfl, bby) == (16, 4 * (8 + 4 + 16))


def test_serving_hit_layer_uses_the_top_layer_width():
    model = {"num_heads": 2, "hidden_dim": 6, "edge_feature_dim": 2,
             "num_classes": 2, "num_layers": 2}
    top = gat_e.forward_flops(WORK, model, 100, first=1)
    bottom = gat_e.forward_flops(WORK, model, 100, first=0)
    assert bottom - top == pytest.approx(2 * 3 * (100 - 6) * 6)
