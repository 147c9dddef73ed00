"""Operations and bytes the algorithms need, counted from shapes.

These count the work the task requires, not what an implementation
happens to do: a kernel that re-reads its inputs or pads its blocks does
more than this, and its share of the roofline falls.
"""
from __future__ import annotations

F32 = 4


def conv_flops(h: int, w: int, k: int, cin: int, cout: int) -> int:
    """Multiply-adds of one SAME convolution producing an h x w map."""
    return 2 * h * w * k * k * cin * cout


def resnet_forward_flops(stage_sizes, widths, hw: int, in_ch: int = 3) -> int:
    """FLOPs of one image through the ResNet feature extractor: the stem,
    every block's two 3x3 convolutions and each 1x1 projection. Norms,
    ReLUs and the pooling are elementwise and left out."""
    total = conv_flops(hw, hw, 3, in_ch, widths[0])
    cin, size = widths[0], hw
    for si, (n, w) in enumerate(zip(stage_sizes, widths)):
        for k in range(n):
            if k == 0 and si > 0:
                size = (size + 1) // 2
            total += conv_flops(size, size, 3, cin, w)
            total += conv_flops(size, size, 3, w, w)
            if cin != w:
                total += conv_flops(size, size, 1, cin, w)
            cin = w
    return total


def greedy_round_work(n: int, d: int, r: int, weighted: bool = False):
    """(flops, bytes) of one k-center round over an (n, d) float32 pool:
    the squared distance of every row to each of ``r`` new centers
    (2 n r d), one read of the pool, the running min-distance read and
    written, the weights if the round is weighted, and the centers with
    their indices."""
    flops = 2 * n * r * d
    nbytes = F32 * (n * d + 2 * n + (n if weighted else 0) + r * d + r)
    return flops, nbytes


def least_time_s(flops: float, nbytes: float, peak_flops: float,
                 hbm_bytes_per_s: float) -> float:
    """The roofline's bound: the larger of compute time and memory time."""
    return max(flops / peak_flops, nbytes / hbm_bytes_per_s)


def al_round_flops(n_unlabeled: int, n_pool: int, d: int, classes: int,
                   budget: int) -> int:
    """The work one AL round (select ``budget`` by k-center, label,
    retrain) requires: fold the ``budget`` newly labeled centers of the
    last round into the unlabeled rows' min-distance, one distance pass
    per pick, and the head's probabilities over the pool after the
    retrain."""
    fold = 2 * n_unlabeled * budget * d
    picks = budget * 2 * n_unlabeled * d
    probs = 2 * n_pool * d * classes
    return fold + picks + probs
