"""Operations and bytes that GCN needs, counted from live nodes, live
edges and the published widths (see ``costs/gat_e.py`` for the
conventions). Per layer: the projection h W over source nodes, the
normalised message per live edge, the sum per destination, bias and
ReLU."""
from __future__ import annotations

F32 = 4


def _dims(model: dict, feature_dim: int):
    return [feature_dim] + [model["hidden_dim"]] * model["num_layers"]


def forward_flops(work: dict, model: dict, feature_dim: int,
                  first: int = 0) -> float:
    """``work["layers"]`` are the model's layers from ``first`` on (the
    serving hit path runs only the top one)."""
    dims = _dims(model, feature_dim)
    total = 0.0
    for k, ly in enumerate(work["layers"], start=first):
        ns, nd, e = ly["n_src"], ly["n_dst"], ly["edges"]
        total += 2.0 * ns * dims[k] * dims[k + 1]     # h W
        total += 1.0 * e * dims[k + 1]                # edge norm
        total += sum_stage_flops(ly, model, feature_dim, k)
        total += 2.0 * nd * dims[k + 1]               # bias, ReLU
    total += 2.0 * work["targets"] * dims[-1] * model["num_classes"]
    return total


def train_flops(work: dict, model: dict, feature_dim: int) -> float:
    return 3.0 * forward_flops(work, model, feature_dim)


def sum_stage_flops(layer: dict, model: dict, feature_dim: int,
                    k: int = 0) -> float:
    return float(layer["edges"]) * model["hidden_dim"]


def sum_stage_passes(work: dict, model: dict, feature_dim: int,
                     train: bool) -> list:
    d = model["hidden_dim"]
    out = []
    for ly in work["layers"]:
        e, nd = ly["edges"], ly["n_dst"]
        fl = sum_stage_flops(ly, model, feature_dim)
        out.append((fl, F32 * (e * d + e + nd * d)))
        if train:
            out.append((fl, F32 * (nd * d + e + e * d)))
    return out
