"""Operations and bytes of the work a round must do, from shapes alone.

FLOPs count the matrix products and convolutions of the client objectives
(two per multiply-add); training is three times the forward pass (the
forward, the gradient of the inputs and of the weights) and nothing that a
program recomputes to save memory is counted.  Each family's count per
sample is its ``client_flops`` (``bench/families/``).  Only real clients
count: a pad slot that a chunk geometry trains at weight 0 is wasted work.

Bytes are what one call of a fold kernel must move through HBM: its
stream of client rows read once, the f32 accumulator read and written, the
mask read.
"""

from __future__ import annotations

from typing import Dict


def _taps(size: int, k: int, stride: int) -> int:
    """Kernel taps over all output positions of one spatial axis of a SAME
    convolution that fall on the input, not on its zero padding."""
    out = -(-size // stride)
    lo = max((out - 1) * stride + k - size, 0) // 2
    return sum(1 for o in range(out) for t in range(k)
               if 0 <= o * stride + t - lo < size)


def conv(size: int, k: int, stride: int, cin: int, cout: int) -> float:
    """FLOPs of one image of a SAME convolution over a ``size`` x ``size``
    input: the multiply-adds of taps that fall on the input, as XLA counts
    them, not those on the zero padding."""
    return 2.0 * _taps(size, k, stride) ** 2 * cin * cout


def round_flops(cfg: dict, traffic: dict, k_simple: int,
                k_complex: int) -> Dict[str, float]:
    """Model FLOPs of one round's local training, by population."""
    from bench import families
    per = families.family(cfg).client_flops
    n, bs = traffic["points_per_client"], traffic["batch_size"]
    samples = traffic["local_epochs"] * max(n // bs, 1) * bs
    simple = k_simple * samples * per(cfg, True)
    complex_ = k_complex * samples * per(cfg, False)
    return {"simple": simple, "complex": complex_,
            "total": simple + complex_}


def fold_bytes(z: int, n_flat: int, stream_itemsize: int) -> float:
    """HBM bytes one accumulating fold of ``z`` rows of ``n_flat`` must move.

    The rows are read once (``stream_itemsize`` bytes each), the f32
    accumulator is read and written (it aliases the output) and the bool
    mask is read."""
    return float(z * n_flat * stream_itemsize + 2 * 4 * n_flat + n_flat)
