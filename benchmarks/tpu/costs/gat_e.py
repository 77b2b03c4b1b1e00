"""Operations and bytes that GAT-E needs, counted from live nodes, live
edges and the published widths: no padding, no pad lanes, no
recomputation. A layer's ``work`` is ``n_src`` (nodes whose projection
feeds an edge), ``n_dst`` (nodes it computes) and ``edges``. Training
counts the backward pass as twice the forward (the usual convention).
Bytes are float32 and int32 reads and writes of the Sum stage: per live
edge its logits, its value row and its destination index; per computed
node its output row (and, backward, the incoming gradient and the
softmax statistics)."""
from __future__ import annotations

F32 = 4


def _dims(model: dict, feature_dim: int):
    H, hid = model["num_heads"], model["hidden_dim"]
    return [feature_dim] + [hid] * model["num_layers"], H, hid // H


def forward_flops(work: dict, model: dict, feature_dim: int,
                  first: int = 0) -> float:
    """``work["layers"]`` are the model's layers from ``first`` on (the
    serving hit path runs only the top one)."""
    dims, H, D = _dims(model, feature_dim)
    Fe, C = model["edge_feature_dim"], model["num_classes"]
    total = 0.0
    for k, ly in enumerate(work["layers"], start=first):
        ns, nd, e = ly["n_src"], ly["n_dst"], ly["edges"]
        total += 2.0 * ns * dims[k] * H * D          # h W
        total += 2.0 * ns * H * D + 2.0 * nd * H * D  # a_src . n, a_dst . n
        total += 2.0 * e * Fe * H + 2.0 * e * Fe * H * D  # edge attributes
        total += 3.0 * e * H + e * H * D             # logit, value
        total += sum_stage_flops(ly, model, feature_dim)
        total += 2.0 * nd * H * D                    # bias, ELU
    total += 2.0 * work["targets"] * dims[-1] * C    # decoder
    return total


def train_flops(work: dict, model: dict, feature_dim: int) -> float:
    return 3.0 * forward_flops(work, model, feature_dim)


def sum_stage_flops(layer: dict, model: dict, feature_dim: int) -> float:
    """Edge softmax forward: max, exp, sum, weighted sum, division."""
    _, H, D = _dims(model, feature_dim)
    return layer["edges"] * H * (3.0 + 2.0 * D) + layer["n_dst"] * H * D


def sum_stage_passes(work: dict, model: dict, feature_dim: int,
                     train: bool) -> list:
    """(flops, bytes) of each Sum-stage pass a step needs: one forward
    per layer and, training, one backward per layer."""
    _, H, D = _dims(model, feature_dim)
    out = []
    for ly in work["layers"]:
        e, nd = ly["edges"], ly["n_dst"]
        fl = sum_stage_flops(ly, model, feature_dim)
        fwd = F32 * (e * H + e * H * D + e + nd * H * D)
        out.append((fl, fwd))
        if train:
            bwd = F32 * (2 * nd * H * D + 2 * nd * H + 2 * e * H
                         + 2 * e * H * D + e)
            out.append((2.0 * fl, bwd))
    return out
